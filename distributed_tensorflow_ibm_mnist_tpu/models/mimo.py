"""A causal LM whose layers name an attention kind AND a feed-forward kind
(the MiMo-V2-Flash family): global or sliding-window attention by layer, a
dense gated MLP or a sigmoid-routed top-k expert layer by layer, of which
this chip holds a share.

Stack (``x`` a layer's input, ``l`` its index; no biases; every RMSNorm with
a learned scale and ``norm_eps``)::

    h0 = E[tok]
    h += Attn_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))
    logits = W_head RMSNorm(h)                           (head untied)

*Attention*, kind by ``layer_kinds[l]`` (0 global, 1 window): q = W_q x as
``heads`` heads of ``head_dim``; k = W_k x as ``Hkv`` heads of ``head_dim``;
v = W_v x as ``Hkv`` heads of ``v_head_dim``, times ``value_scale``;
``Hkv`` = ``heads_kv`` on a global layer, ``window_heads_kv`` on a window
layer.  Rotary embedding on the first ``rotary_dim`` dimensions of q and k
(``apply_rope``'s pair layout), base ``rope_theta`` on a global layer and
``window_rope_theta`` on a window layer; the other dimensions pass.  Scores
q.k / sqrt(head_dim), causal; on a window layer query i sees keys j with
``i - window < j <= i`` and the softmax runs over those scores AND one
learned logit per head (the sink), whose column is dropped after
normalisation; a global layer has no sink.  Output W_o (heads x v_head_dim
-> dim).

*FFN*, kind by ``ffn_kinds[l]``: 0 -> ``W_down(silu(W_gate u) * W_up u)`` at
``intermediate``.  1 -> ``parallel/expert_parallel.py``: router logits
``W_r u`` in float32, ``s = sigmoid(logits)``, the ``top_k`` experts with
the largest ``s + b`` (``b`` the correction bias), weights ``s_e`` over the
sum of the chosen ``s``, ``y = sum_e w_e W_down_e(silu(W_gate_e u) *
W_up_e u)`` at ``expert_intermediate``, no shared expert.  The router is
``n_experts`` wide; the banks hold ``held_experts`` experts from
``held_first`` on, and the layer returns THEIR part of ``y``: the partial sum
of one chip of an expert-parallel deployment, which is what goes on to the
next layer.

The model is a SERVING model: it decodes through the engine's caches
(``decode=True``), whose leaves it declares per layer —

    global   pages_k (n_pages, page, Hkv, Dk'), pages_v (n_pages, page, Hkv,
             Dv)   the page pool; Dk' = head_dim zero-padded to whole lanes
             (192 -> 256: the paged kernel reads whole 128-lane rows; q is
             padded alike, the arithmetic is the same)
             block_table (B, max_len / page), index (B,)
    window   ring_k (B, window, Hkv_w, Dk), ring_v (B, window, Hkv_w, Dv):
             position p of a row lives at ring slot p mod window; a window
             layer owns no page
             index (B,)
    expert   expert_load (2, held_experts) int32, added to on the device
             since the engine started: the pairs each held expert has
             computed, and the calls (decode steps, chunks) that gave it any
             (each such call reads the expert's matrices once)
             chosen (B, top_k) int32: the last decode step's chosen experts

plus ``n_valid`` (B,), which the engine sets for each call (models/sala.py:
0 for a row that is idle or prefilling).  A call with one token a row is a
decode step over all rows; a call with more is ONE row's prefill chunk at
its cursor, a whole number of windows long, whose first chunk reads no ring
(positions below 0 are masked), so a slot's last tenant leaves nothing
behind.  The plain forward (``decode=False``) exists for ``init`` and for
short sequences; training needs a backward through the dropless layer,
which this repo does not have.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.models.paged_layer import (
    Widths,
    attend,
    lane_pad,
    paged_chunk,
    paged_step,
)
from distributed_tensorflow_ibm_mnist_tpu.models.sala import RMSNorm, _external
from distributed_tensorflow_ibm_mnist_tpu.models.transformer import apply_rope
from distributed_tensorflow_ibm_mnist_tpu.parallel.expert_parallel import (
    dropless_held_ffn,
    sigmoid_topk_route,
)


class MimoBlock(nn.Module):
    windowed: bool
    experts: bool
    dim: int
    heads: int
    heads_kv: int          # this layer's own
    head_dim: int
    v_head_dim: int
    rotary_dim: int
    rope_theta: float      # this layer's own
    window: int
    value_scale: float
    intermediate: int
    expert_intermediate: int
    n_experts: int
    top_k: int
    held_first: int
    held_experts: int
    norm_eps: float
    page_size: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    def _dense(self, features, name):
        return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, decode: bool = False, max_len: int = 0):
        idx_var = n_valid = None
        if decode:
            idx_var = self.variable("cache", "index", _external("index"))
            n_valid = self.variable("cache", "n_valid", _external("n_valid")).value
        h = RMSNorm(self.norm_eps, self.dtype, name="norm_attn")(x)
        x = x + self._attention(h, idx_var, n_valid, max_len).astype(x.dtype)
        u = RMSNorm(self.norm_eps, self.dtype, name="norm_mlp")(x)
        ffn = self._experts if self.experts else self._mlp
        return x + ffn(u, n_valid).astype(x.dtype)

    # ------------------------------------------------------------ feed-forward
    def _mlp(self, u, n_valid):
        del n_valid
        g = self._dense(self.intermediate, "mlp_gate")(u)
        return self._dense(self.dim, "mlp_down")(
            nn.silu(g) * self._dense(self.intermediate, "mlp_up")(u))

    def _experts(self, u, n_valid):
        b, s, d = u.shape
        f, n_held = self.expert_intermediate, self.held_experts
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        w_r = self.param("router", nn.initializers.lecun_normal(),
                         (d, self.n_experts))
        bias = self.param("router_bias", nn.initializers.zeros,
                          (self.n_experts,))
        banks = [self.param(name, init, shape).astype(self.dtype)
                 for name, shape in (("experts_gate", (n_held, d, f)),
                                     ("experts_up", (n_held, d, f)),
                                     ("experts_down", (n_held, f, d)))]
        flat = u.reshape(b * s, d)
        ids, w = sigmoid_topk_route(flat, w_r, bias, self.top_k)
        valid = None
        if n_valid is not None:
            # a decode step: the rows that decode; a chunk: its real tokens
            valid = ((n_valid > 0) if s == 1
                     else jnp.arange(s) < n_valid[0]).reshape(-1)
        y, load = dropless_held_ffn(flat, ids, w, *banks, self.held_first, valid)
        if n_valid is not None:
            total = self.variable("cache", "expert_load", _external("expert_load"))
            total.value = total.value + jnp.stack([load, load > 0]).astype(jnp.int32)
            if s == 1:
                self.variable("cache", "chosen", _external("chosen")).value = ids
        return y.reshape(b, s, d)

    # --------------------------------------------------------------- attention
    def _attention(self, h, idx_var, n_valid, max_len):
        b, s, _ = h.shape
        nh, hkv, dk, dv = self.heads, self.heads_kv, self.head_dim, self.v_head_dim
        # the projections come out two-dimensional BEFORE heads of 192 are cut
        # from them: left to fuse the reshape into the product, the compiler
        # re-lays the 100 MB weight out for it on every call (2 ms of a 15.6
        # ms decode window on the v5e) instead of the few-MB activation
        q, k, v = jax.lax.optimization_barrier(tuple(
            self._dense(n, name)(h) for n, name in (
                (nh * dk, "q_proj"), (hkv * dk, "k_proj"), (hkv * dv, "v_proj"))))
        q, k, v = (x.reshape(b, s, n, d) for x, n, d in (
            (q, nh, dk), (k, hkv, dk), (v, hkv, dv)))
        v = (v * self.value_scale).astype(self.dtype)
        sink = (self.param("sink", nn.initializers.zeros, (nh,))
                if self.windowed else None)
        if idx_var is not None:
            idx = idx_var.value
            q, k = self._rope(q, idx), self._rope(k, idx)
            idx_var.value = jnp.minimum(idx + n_valid, max_len)
            if self.windowed:
                step = self._ring_step if s == 1 else self._ring_chunk
                o = step(q, k, v, sink, idx, n_valid)
            elif s == 1:
                o = paged_step(self, q, k, v, idx, n_valid, max_len, self._widths())
            else:
                o = paged_chunk(self, q, k, v, idx, max_len, self._widths())
        else:
            q, k = self._rope(q, 0), self._rope(k, 0)
            o = self._plain(q, k, v, sink)
        return self._dense(self.dim, "o_proj")(
            o.reshape(b, s, nh * dv).astype(self.dtype))

    def _rope(self, x, offset):
        r = self.rotary_dim
        return jnp.concatenate(
            [apply_rope(x[..., :r], self.rope_theta, offset=offset), x[..., r:]], -1)

    def _widths(self) -> Widths:
        return Widths(self.heads, self.heads_kv, self.head_dim,
                      self.v_head_dim, self.page_size, self.dtype)

    def _plain(self, q, k, v, sink):
        s = q.shape[1]
        i = jnp.arange(s)
        allow = i[None, :] <= i[:, None]
        if self.windowed:
            allow = allow & (i[None, :] > i[:, None] - self.window)
        return attend(q, k, v, allow[None], self.head_dim, sink)

    # ---- window layers: a ring of the last ``window`` positions per row
    def _rings(self):
        return (self.variable("cache", "ring_k", _external("ring_k")),
                self.variable("cache", "ring_v", _external("ring_v")))

    def _ring_step(self, q, k, v, sink, idx, n_valid):
        """One token a row, all rows: write position t at slot t mod window
        (a row that is not decoding writes nowhere), attend to the ring."""
        ring_k, ring_v = self._rings()
        w = self.window
        b = q.shape[0]
        slot = jnp.where(n_valid > 0, idx % w, w)
        rows = jnp.arange(b)
        ring_k.value = ring_k.value.at[rows, slot].set(k[:, 0], mode="drop")
        ring_v.value = ring_v.value.at[rows, slot].set(v[:, 0], mode="drop")
        j = jnp.arange(w)
        # slot j holds the newest position <= t that is congruent to j
        held = idx[:, None] - (idx[:, None] - j[None, :]) % w
        return attend(q, ring_k.value, ring_v.value, (held >= 0)[:, None, :],
                      self.head_dim, sink)

    def _ring_chunk(self, q, k, v, sink, idx, n_valid):
        """ONE row's prefill chunk, a whole number of windows that starts on
        a window's edge: each tile of ``window`` queries against its own and
        the previous ``window`` keys, the first tile's previous ones being
        the ring (positions start - window .. start - 1, in slot order)."""
        ring_k, ring_v = self._rings()
        w = self.window
        b, c = q.shape[:2]
        if b != 1 or c % w:
            raise ValueError(
                f"a prefill chunk is one row of whole windows ({w}), got {b} "
                f"rows of {c} tokens")
        start, n = idx[0], n_valid[0]
        tiles = c // w
        # positions start - w .. start + c - 1, position-major
        k_ext = jnp.concatenate([ring_k.value[0], k[0]])
        v_ext = jnp.concatenate([ring_v.value[0], v[0]])

        def pairs(x):  # (tiles, 2 w, ...): tile i's previous and own keys
            prev = x[:c].reshape((tiles, w) + x.shape[1:])
            own = x[w:].reshape((tiles, w) + x.shape[1:])
            return jnp.concatenate([prev, own], axis=1)

        i = jnp.arange(w)
        qpos = start + (jnp.arange(tiles) * w)[:, None] + i[None, :]      # (tiles, w)
        kpos = qpos[:, :1] - w + jnp.arange(2 * w)[None, :]               # (tiles, 2w)
        allow = ((kpos[:, None, :] <= qpos[:, :, None])
                 & (kpos[:, None, :] > qpos[:, :, None] - w)
                 & (kpos[:, None, :] >= 0))
        o = attend(q[0].reshape((tiles, w) + q.shape[2:]), pairs(k_ext),
                   pairs(v_ext), allow, self.head_dim, sink)
        # the ring after the chunk: slot j takes the newest REAL position
        # congruent to j (from this chunk or, in a short last chunk, the
        # ring as it was)
        end = start + n
        newest = end - 1 - (end - 1 - i) % w
        at = jnp.clip(newest - (start - w), 0, c + w - 1)
        ring_k.value = k_ext[at][None]
        ring_v.value = v_ext[at][None]
        return o.reshape((1, c) + o.shape[2:])


class MimoLM(nn.Module):
    """Embed -> blocks of (``layer_kinds``, ``ffn_kinds``) -> RMSNorm ->
    untied head."""

    num_classes: int = 64  # vocabulary size (named for zoo consistency)
    dim: int = 128
    layer_kinds: tuple = (0, 1, 1)   # 0 global, 1 window
    ffn_kinds: tuple = (0, 1, 1)     # 0 dense, 1 experts
    heads: int = 4
    heads_kv: int = 2                # a global layer's
    window_heads_kv: int = 4         # a window layer's
    head_dim: int = 192
    v_head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 5e6
    window_rope_theta: float = 1e4
    window: int = 128
    value_scale: float = 0.707
    intermediate: int = 256
    expert_intermediate: int = 64
    n_experts: int = 16
    top_k: int = 4
    held_first: int = 0       # the first expert this chip holds
    held_experts: int = 4     # and how many, consecutive
    norm_eps: float = 1e-5
    page_size: int = 0  # set by the serving engine on its decode clone
    paged_one_device: bool = False  # accepted for the engine's clone; the
    #   paged kernel is this model's only read path for a decode step, so a
    #   mesh is refused by the engine, not here
    dtype: jnp.dtype = jnp.bfloat16

    has_recurrent_state = True  # per-row leaves beside the page pool: the
    #   engine tells each call which rows are real (n_valid) and prefills in
    #   chunks only
    has_window_rings = True     # which per-row leaves, for its counters
    has_expert_layers = True    # and an expert_load leaf it reads back

    @property
    def depth(self) -> int:
        return len(self.layer_kinds)

    def _check(self):
        if len(self.ffn_kinds) != self.depth:
            raise ValueError(
                f"ffn_kinds names {len(self.ffn_kinds)} layers, layer_kinds "
                f"{self.depth}")
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
                "MimoLM decodes through the paged cache only: the engine "
                "needs kv_page_size > 0")
        x = nn.Embed(self.num_classes, self.dim, dtype=self.dtype,
                     name="embed")(tokens.astype(jnp.int32))
        for i, (kind, ffn) in enumerate(zip(self.layer_kinds, self.ffn_kinds)):
            x = MimoBlock(
                windowed=bool(kind), experts=bool(ffn), dim=self.dim,
                heads=self.heads,
                heads_kv=self.window_heads_kv if kind else self.heads_kv,
                head_dim=self.head_dim, v_head_dim=self.v_head_dim,
                rotary_dim=self.rotary_dim,
                rope_theta=self.window_rope_theta if kind else self.rope_theta,
                window=self.window, value_scale=self.value_scale,
                intermediate=self.intermediate,
                expert_intermediate=self.expert_intermediate,
                n_experts=self.n_experts, top_k=self.top_k,
                held_first=self.held_first, held_experts=self.held_experts,
                norm_eps=self.norm_eps, page_size=self.page_size,
                dtype=self.dtype, name=f"block_{i}")(x, decode, max_len)
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_out")(x)
        x = nn.Dense(self.num_classes, use_bias=False, dtype=self.dtype,
                     name="logits")(x)
        return x.astype(jnp.float32)

    def decode_read_plan(self, ctx):
        """Pages one decode window's global layers read, for the engine's
        counter: ``ctx`` (rows, steps) int contexts (position + 1) of the
        decoding rows at each step.  A page holds every KV head, so a
        layer reads ``ceil(ctx / page)`` pages a row and step."""
        live = (np.asarray(ctx) - 1) // self.page_size + 1
        return int(live.sum()) * self.layer_kinds.count(0)

    def expert_pairs(self, n_tokens: int) -> int:
        """(token, choice) pairs ``n_tokens`` tokens make over the expert
        layers, held here or not."""
        return int(n_tokens) * self.top_k * sum(self.ffn_kinds)

    def paged_cache_shapes(self, slots: int, max_len: int, page_size: int,
                           n_pages: int) -> dict:
        """The decode cache's leaves per block (serving/kv_pool.py asks a
        model that has this method): K/V pages for the global layers, a
        ring a row for the window layers, the load counter and the last
        step's choice for the expert layers."""
        struct = jax.ShapeDtypeStruct
        out = {}
        for i, (kind, ffn) in enumerate(zip(self.layer_kinds, self.ffn_kinds)):
            e = {"index": struct((slots,), jnp.int32)}
            if kind:
                hkv = self.window_heads_kv
                e["ring_k"] = struct((slots, self.window, hkv, self.head_dim), self.dtype)
                e["ring_v"] = struct((slots, self.window, hkv, self.v_head_dim), self.dtype)
            else:
                e["pages_k"] = struct((n_pages, page_size, self.heads_kv,
                                       lane_pad(self.head_dim)), self.dtype)
                e["pages_v"] = struct((n_pages, page_size, self.heads_kv,
                                       self.v_head_dim), self.dtype)
                e["block_table"] = struct((slots, max_len // page_size), jnp.int32)
            if ffn:
                e["expert_load"] = struct((2, self.held_experts), jnp.int32)
                e["chosen"] = struct((slots, self.top_k), jnp.int32)
            out[f"block_{i}"] = e
        return out
