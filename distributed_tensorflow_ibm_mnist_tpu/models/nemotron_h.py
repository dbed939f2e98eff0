"""A causal LM whose layers are of three kinds, one mixer OR one
feed-forward each, chosen per layer by a pattern (the Nemotron-H family,
Nemotron-3-Super among it): Mamba-2 state-space layers, latent expert
layers of which this chip holds a share, and a few attention layers.

Stack (``x`` a layer's input; no biases but the convolution's; every
RMSNorm with a learned scale and ``norm_eps``)::

    h0 = E[tok]
    h += F_l(RMSNorm(h))        F_l by pattern[l]: "M", "E" or "*"
    logits = W_head RMSNorm(h)                           (head untied)

``"M"`` — Mamba-2 (SSD), ``heads`` heads of ``head_dim`` (d_in = heads x
head_dim), ``groups`` groups of ``B`` / ``C`` of width ``state``::

    [z | xBC | dt] = W_in u          z: d_in, xBC: d_in + 2 groups state,
                                     dt: heads
    xBC = silu(causal depthwise conv over conv_kernel taps (xBC) + b)
    x, B, C = split(xBC)             head i reads group i // (heads / groups)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    F = W_out RMSNorm_groups(y * silu(z))   (the norm taken per group of
                                              d_in / groups channels)

in float32 from the projection's output on (``ops/ssd.py``).  ``"*"`` —
grouped-query attention, ``attn_heads`` query and ``attn_heads_kv`` KV heads
of ``attn_head_dim``, causal, scores / sqrt(attn_head_dim), no rotary
embedding, through the page pool as MiMo's global layers
(``models/paged_layer.py``: the same pages, chunk attention and paged
decode kernel).  ``"E"`` — a latent expert
layer (``parallel/expert_parallel.py``)::

    s = sigmoid(W_r u) (float32, n_experts wide); the top_k of s + b
    w = routed_scale * s_sel / sum(s_sel)
    F = W_lat_up sum_e w_e W2_e relu(W1_e (W_lat_down u))^2
        + W2_s relu(W1_s u)^2                             (the shared expert)

The router is ``n_experts`` wide; the banks hold ``held_experts`` experts
from ``held_first`` on, and the routed part is THEIR share: the partial sum
of one chip of an expert-parallel deployment, to which the shared expert is
added here as on every chip.

The model is a SERVING model: it decodes through the engine's caches
(``decode=True``), whose leaves it declares per layer —

    "M"  ssm_state (B, heads, head_dim, state) float32, conv_state (B,
         conv_kernel - 1, d_in + 2 groups state) in the compute dtype: the
         last inputs of the convolution; index (B,)
    "*"  pages_k, pages_v (n_pages, page, Hkv, D), block_table (B, max_len
         / page), index (B,)
    "E"  expert_load (2, held_experts) int32 and chosen (B, top_k) int32 as
         models/mimo.py's; with ``record_chunk`` > 0 also chunk_chosen
         (record_chunk, top_k) int32: the experts each token of the last
         prefill chunk chose (the first ``record_chunk`` tokens of it; the
         serving check reads it); index (B,) (unused: the engine keeps
         every layer's cursor)

plus ``n_valid`` (B,), which the engine sets for each call (models/sala.py:
0 for a row that is idle or prefilling).  A call with one token a row is a
decode step over all rows; a call with more is ONE row's prefill chunk at
its cursor, whose first chunk (``start == 0``) begins from a zero state and
a zero convolution whatever the slot's last tenant left.  The plain forward
(``decode=False``) exists for ``init`` and for short sequences; training
needs the scan's backward, which this repo does not have.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_ibm_mnist_tpu.models.paged_layer import (
    Widths,
    attend,
    paged_chunk,
    paged_step,
)
from distributed_tensorflow_ibm_mnist_tpu.models.sala import RMSNorm, _external
from distributed_tensorflow_ibm_mnist_tpu.ops.ssd import (
    causal_conv,
    ssd_chunk_scan,
    ssd_step,
)
from distributed_tensorflow_ibm_mnist_tpu.parallel.expert_parallel import (
    dropless_held_ffn,
    sigmoid_topk_route,
)

KINDS = ("M", "E", "*")


def _cursor(module):
    """(index variable, n_valid) of a decode-mode call."""
    return (module.variable("cache", "index", _external("index")),
            module.variable("cache", "n_valid", _external("n_valid")).value)


class GroupRMSNorm(nn.Module):
    """RMSNorm taken per group of ``width / groups`` channels, float32."""
    groups: int
    eps: float

    @nn.compact
    def __call__(self, y):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        g = y.reshape(y.shape[:-1] + (self.groups, -1))
        g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + self.eps)
        return g.reshape(y.shape) * scale.astype(jnp.float32)


class MambaBlock(nn.Module):
    dim: int
    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int
    norm_eps: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False, max_len: int = 0):
        b, s, _ = x.shape
        nh, p, n, g, k = (self.heads, self.head_dim, self.state, self.groups,
                          self.conv_kernel)
        d_in = nh * p
        width = d_in + 2 * g * n
        h = RMSNorm(self.norm_eps, self.dtype, name="norm")(x)
        zxbcdt = nn.Dense(d_in + width + nh, use_bias=False, dtype=self.dtype,
                          name="in_proj")(h)
        z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + width], axis=-1)
        w_conv = self.param("conv_weight", nn.initializers.lecun_normal(),
                            (k, width))
        b_conv = self.param("conv_bias", nn.initializers.zeros, (width,))
        a_log = self.param("A_log", nn.initializers.zeros, (nh,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (nh,))
        d_skip = self.param("D", nn.initializers.ones, (nh,))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        if decode:
            idx_var, n_valid = _cursor(self)
            conv = self.variable("cache", "conv_state", _external("conv_state"))
            ssm = self.variable("cache", "ssm_state", _external("ssm_state"))
            idx = idx_var.value
            idx_var.value = jnp.minimum(idx + n_valid, max_len)
            if s > 1 and b != 1:
                raise ValueError(
                    f"a prefill chunk is one row, got {b} rows of {s} tokens")
            tail = conv.value
            if s > 1:
                # a row's first chunk starts from nothing, whatever the
                # slot's last tenant left in the pool
                tail = jnp.where(idx[0] > 0, tail, 0)
            u, conv.value = causal_conv(xbc, tail, w_conv, b_conv, n_valid)
            x_, bm, cm = self._split(jax.nn.silu(u))
            if s == 1:
                y, ssm.value = ssd_step(x_[:, 0], dt[:, 0], a_log, bm[:, 0],
                                        cm[:, 0], ssm.value, n_valid > 0)
                y = y[:, None]
            else:
                s0 = jnp.where(idx[0] > 0, ssm.value[0], 0.0)
                y, s1 = ssd_chunk_scan(
                    x_[0].transpose(1, 0, 2), dt[0].T, a_log,
                    bm[0].transpose(1, 0, 2), cm[0].transpose(1, 0, 2), s0,
                    n_valid[0])
                y = y.transpose(1, 0, 2)[None]
                ssm.value = s1[None]
        else:
            u, _ = causal_conv(xbc, jnp.zeros((b, k - 1, width), xbc.dtype),
                               w_conv, b_conv, jnp.full((b,), s))
            x_, bm, cm = self._split(jax.nn.silu(u))
            rows = [ssd_chunk_scan(
                x_[r].transpose(1, 0, 2), dt[r].T, a_log,
                bm[r].transpose(1, 0, 2), cm[r].transpose(1, 0, 2),
                jnp.zeros((nh, p, n), jnp.float32), s)[0].transpose(1, 0, 2)
                for r in range(b)]
            y = jnp.stack(rows)
        y = y + d_skip.astype(jnp.float32)[:, None] * x_
        y = y.reshape(b, s, d_in) * jax.nn.silu(z.astype(jnp.float32))
        y = GroupRMSNorm(g, self.norm_eps, name="norm_gated")(y)
        return x + nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y.astype(self.dtype)).astype(x.dtype)

    def _split(self, xbc):
        """(x (B, S, heads, head_dim), B, C (B, S, groups, state)), float32."""
        b, s, _ = xbc.shape
        d_in, gn = self.heads * self.head_dim, self.groups * self.state
        x, bm, cm = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
        return (x.reshape(b, s, self.heads, self.head_dim),
                bm.reshape(b, s, self.groups, self.state),
                cm.reshape(b, s, self.groups, self.state))


class LatentMoEBlock(nn.Module):
    dim: int
    latent: int
    expert_intermediate: int
    shared_intermediate: int
    n_experts: int
    top_k: int
    routed_scale: float
    held_first: int
    held_experts: int
    norm_eps: float
    record_chunk: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    def _dense(self, features, name):
        return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, decode: bool = False, max_len: int = 0):
        del max_len
        b, s, d = x.shape
        n_valid = (self.variable("cache", "n_valid", _external("n_valid")).value
                   if decode else None)
        u = RMSNorm(self.norm_eps, self.dtype, name="norm")(x)
        f, n_held = self.expert_intermediate, self.held_experts
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        w_r = self.param("router", nn.initializers.lecun_normal(),
                         (d, self.n_experts))
        bias = self.param("router_bias", nn.initializers.zeros, (self.n_experts,))
        up = self.param("experts_up", init, (n_held, self.latent, f)).astype(self.dtype)
        down = self.param("experts_down", init, (n_held, f, self.latent)).astype(self.dtype)
        flat = u.reshape(b * s, d)
        ids, w = sigmoid_topk_route(flat, w_r, bias, self.top_k)
        valid = None
        if n_valid is not None:
            # a decode step: the rows that decode; a chunk: its real tokens
            valid = ((n_valid > 0) if s == 1
                     else jnp.arange(s) < n_valid[0]).reshape(-1)
        lat = self._dense(self.latent, "latent_down")(flat)
        y, load = dropless_held_ffn(lat, ids, w * self.routed_scale, None, up,
                                    down, self.held_first, valid)
        y = self._dense(d, "latent_up")(y)
        shared = self._dense(d, "shared_down")(jnp.square(jax.nn.relu(
            self._dense(self.shared_intermediate, "shared_up")(flat))))
        if n_valid is not None:
            total = self.variable("cache", "expert_load", _external("expert_load"))
            total.value = total.value + jnp.stack([load, load > 0]).astype(jnp.int32)
            if s == 1:
                self.variable("cache", "chosen", _external("chosen")).value = ids
            elif self.record_chunk:
                rec = self.variable("cache", "chunk_chosen", _external("chunk_chosen"))
                rec.value = jax.lax.dynamic_update_slice(
                    rec.value, ids[:self.record_chunk], (0, 0))
        return x + (y + shared).reshape(b, s, d).astype(x.dtype)


class AttentionBlock(nn.Module):
    """Grouped-query attention, causal, position-free, under a pre-norm
    residual; decoding through the page pool (``models/paged_layer.py``)."""
    dim: int
    heads: int
    heads_kv: int
    head_dim: int
    norm_eps: float
    page_size: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False, max_len: int = 0):
        b, s, _ = x.shape
        nh, hkv, d = self.heads, self.heads_kv, self.head_dim
        h = RMSNorm(self.norm_eps, self.dtype, name="norm")(x)
        # the projections come out two-dimensional before heads are cut
        # from them (models/mimo.py: the compiler otherwise re-lays the
        # weight out for the reshape on every call)
        q, k, v = jax.lax.optimization_barrier(tuple(
            nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)(h)
            for n, name in ((nh * d, "q_proj"), (hkv * d, "k_proj"),
                            (hkv * d, "v_proj"))))
        q, k, v = (y.reshape(b, s, n, d) for y, n in ((q, nh), (k, hkv), (v, hkv)))
        if decode:
            idx_var, n_valid = _cursor(self)
            idx = idx_var.value
            idx_var.value = jnp.minimum(idx + n_valid, max_len)
            w = Widths(nh, hkv, d, d, self.page_size, self.dtype)
            o = (paged_step(self, q, k, v, idx, n_valid, max_len, w) if s == 1
                 else paged_chunk(self, q, k, v, idx, max_len, w))
        else:
            i = jnp.arange(s)
            o = attend(q, k, v, (i[None, :] <= i[:, None])[None], d)
        o = nn.Dense(self.dim, use_bias=False, dtype=self.dtype, name="o_proj")(
            o.reshape(b, s, nh * d).astype(self.dtype))
        return x + o.astype(x.dtype)


class NemotronHLM(nn.Module):
    """Embed -> blocks of ``pattern`` -> RMSNorm -> untied head."""

    num_classes: int = 64  # vocabulary size (named for zoo consistency)
    dim: int = 64
    pattern: str = "MEM*E"
    heads: int = 4            # Mamba heads
    head_dim: int = 16        # and their width
    state: int = 32
    groups: int = 2
    conv_kernel: int = 4
    attn_heads: int = 4
    attn_heads_kv: int = 2
    attn_head_dim: int = 128
    latent: int = 32
    expert_intermediate: int = 48
    shared_intermediate: int = 96
    n_experts: int = 16
    top_k: int = 4
    routed_scale: float = 5.0
    held_first: int = 0       # the first expert this chip holds
    held_experts: int = 4     # and how many, consecutive
    norm_eps: float = 1e-5
    record_chunk: int = 0     # > 0: each expert layer keeps the ids the
    #   first record_chunk tokens of the last prefill chunk chose
    page_size: int = 0  # set by the serving engine on its decode clone
    paged_one_device: bool = False  # accepted for the engine's clone; the
    #   paged kernel is the attention layers' only read path for a decode
    #   step, so a mesh is refused by the engine, not here
    dtype: jnp.dtype = jnp.bfloat16

    has_recurrent_state = True  # per-row leaves beside the page pool: the
    #   engine tells each call which rows are real (n_valid) and prefills in
    #   chunks only
    has_expert_layers = True    # and an expert_load leaf it reads back

    @property
    def depth(self) -> int:
        return len(self.pattern)

    @property
    def ssm_layers(self) -> int:
        """Mamba layers, for the engine's counters of the scan and the
        decode step's update."""
        return self.pattern.count("M")

    def _check(self):
        for c in self.pattern:
            if c not in KINDS:
                raise ValueError(f"unknown layer kind {c!r}; use one of {KINDS}")
        if not 0 <= self.held_first <= self.n_experts - self.held_experts:
            raise ValueError(
                f"held experts {self.held_first}..{self.held_first + self.held_experts - 1}"
                f" are not among the router's {self.n_experts}")

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False,
                 max_len: int = 0, ragged: bool = False):
        del train, ragged  # no dropout; every row owns its cursor
        self._check()
        if decode and not self.page_size:
            raise ValueError(
                "NemotronHLM decodes through the paged cache only: the engine "
                "needs kv_page_size > 0")
        x = nn.Embed(self.num_classes, self.dim, dtype=self.dtype,
                     name="embed")(tokens.astype(jnp.int32))
        for i, kind in enumerate(self.pattern):
            x = self._block(kind, f"block_{i}")(x, decode, max_len)
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_out")(x)
        x = nn.Dense(self.num_classes, use_bias=False, dtype=self.dtype,
                     name="logits")(x)
        return x.astype(jnp.float32)

    def _block(self, kind, name):
        if kind == "M":
            return MambaBlock(
                dim=self.dim, heads=self.heads, head_dim=self.head_dim,
                state=self.state, groups=self.groups,
                conv_kernel=self.conv_kernel, norm_eps=self.norm_eps,
                dtype=self.dtype, name=name)
        if kind == "E":
            return LatentMoEBlock(
                dim=self.dim, latent=self.latent,
                expert_intermediate=self.expert_intermediate,
                shared_intermediate=self.shared_intermediate,
                n_experts=self.n_experts, top_k=self.top_k,
                routed_scale=self.routed_scale, held_first=self.held_first,
                held_experts=self.held_experts, norm_eps=self.norm_eps,
                record_chunk=self.record_chunk, dtype=self.dtype, name=name)
        return AttentionBlock(
            dim=self.dim, heads=self.attn_heads, heads_kv=self.attn_heads_kv,
            head_dim=self.attn_head_dim, norm_eps=self.norm_eps,
            page_size=self.page_size, dtype=self.dtype, name=name)

    def expert_pairs(self, n_tokens: int) -> int:
        """(token, choice) pairs ``n_tokens`` tokens make over the expert
        layers, held here or not."""
        return int(n_tokens) * self.top_k * self.pattern.count("E")

    def paged_cache_shapes(self, slots: int, max_len: int, page_size: int,
                           n_pages: int) -> dict:
        """The decode cache's leaves per block (serving/kv_pool.py asks a
        model that has this method): a state and a convolution tail a row
        for the Mamba layers, K/V pages for the attention layers, the load
        counter and the last step's choice for the expert layers (and the
        last chunk's, with ``record_chunk``)."""
        struct = jax.ShapeDtypeStruct
        width = self.heads * self.head_dim + 2 * self.groups * self.state
        out = {}
        for i, kind in enumerate(self.pattern):
            e = {"index": struct((slots,), jnp.int32)}
            if kind == "M":
                e["ssm_state"] = struct((slots, self.heads, self.head_dim,
                                         self.state), jnp.float32)
                e["conv_state"] = struct((slots, self.conv_kernel - 1, width),
                                         self.dtype)
            elif kind == "*":
                pool = struct((n_pages, page_size, self.attn_heads_kv,
                               self.attn_head_dim), self.dtype)
                e["pages_k"] = e["pages_v"] = pool
                e["block_table"] = struct((slots, max_len // page_size), jnp.int32)
            else:
                e["expert_load"] = struct((2, self.held_experts), jnp.int32)
                e["chosen"] = struct((slots, self.top_k), jnp.int32)
                if self.record_chunk:
                    e["chunk_chosen"] = struct((self.record_chunk, self.top_k),
                                               jnp.int32)
            out[f"block_{i}"] = e
        return out
