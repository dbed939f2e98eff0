"""A causal LM whose layers are of two kinds, chosen per layer (the
MiniCPM-SALA family): block-sparse softmax attention over a paged KV cache,
and lightning (linear) attention over a fixed-size recurrent state.

Stack (x is a block's input, ``r = scale_depth / sqrt(residual_layers)``)::

    h0 = scale_emb * E[tok]
    h += r * Mixer_l(RMSNorm(h))
    h += r * W_down(silu(W_gate u) * W_up u),  u = RMSNorm(h)
    logits = W_head(RMSNorm(h) / logit_divisor)          (head untied)

No biases.  ``mixer_types[l]`` names layer l's mixer:

``"minicpm4"`` — q: ``heads`` x ``head_dim``, k, v: ``heads_kv`` x
``head_dim``; RMSNorm over the head dimension on q and k; no rotary
embedding; scores / sqrt(head_dim); causal.  A query whose context is at
most ``sparse.dense_len`` attends to all of it; a longer one to the blocks
``ops/sparse_attention.py`` selects for it (float32 selection from
compressed keys).  ``y = W_o(o * sigmoid(W_g x))``.

``"lightning-attn"`` — q, k, v: ``lightning_heads`` x ``head_dim`` each;
the same qk-norm; rotary embedding (``rope_theta``) on q and k; per head
``S_t = lam_h S_{t-1} + k_t^T v_t`` in float32 and ``o_t = (q_t /
sqrt(D)) S_t`` (``ops/lightning_attention.py``);
``y = W_o(RMSNorm(concat_h o) * sigmoid(W_g x))``.

The model is a SERVING model: it decodes through the engine's caches
(``decode=True``), whose leaves it declares per layer kind —

    minicpm4        pages_k, pages_v (n_pages, page, Hkv, D)   the page pool
                    block_table (B, max_len / page), index (B,)
                    kc (B, max_len / stride, Hkv, D) float32    compressed keys
                    sel (B, Hkv, W), sel_len (B, Hkv) int32     the last decode
                    step's block ids and list length, as the kernel got them
    lightning-attn  state (B, H, D, D) float32, index (B,)

plus ``n_valid`` (B,), which the engine sets for each call: how many of the
call's tokens are real for each row (0 for a row that is idle or still
prefilling: such a row writes nothing and keeps its state).  A call with
one token a row is a decode step over all rows; a call with more is ONE
row's prefill chunk at its cursor (``start = index``), whose first chunk
(``start == 0``) begins from a zero state whatever the slot's last tenant
left.  The plain forward (``decode=False``) exists for ``init`` and for
short sequences (at most ``dense_len``); training needs the scan's backward,
which this repo does not have.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.models.transformer import apply_rope
from distributed_tensorflow_ibm_mnist_tpu.ops import sparse_attention as sparse_ops
from distributed_tensorflow_ibm_mnist_tpu.ops.lightning_attention import (
    lightning_chunk_scan,
    lightning_slopes,
    lightning_step,
)
from distributed_tensorflow_ibm_mnist_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_kernel_eligible,
)
from distributed_tensorflow_ibm_mnist_tpu.ops.sparse_attention import SparseSpec

MIXERS = ("minicpm4", "lightning-attn")
_SELECT_TILE = 256  # queries whose block scores are in memory at once


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


def _external(name):
    def init():
        raise ValueError(
            f"decode cache variable {name!r} must be supplied by the caller: "
            "the page pool and the state pool are engine state "
            "(serving.kv_pool.init_paged_cache)")
    return init


class SalaBlock(nn.Module):
    mixer: str
    dim: int
    heads: int
    heads_kv: int
    head_dim: int
    lightning_heads: int
    intermediate: int
    norm_eps: float
    rope_theta: float
    residual_scale: float
    sparse: SparseSpec
    page_size: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    def _dense(self, features, name):
        return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)

    def _norm(self, name, dtype=None):
        return RMSNorm(self.norm_eps, dtype or self.dtype, name=name)

    @nn.compact
    def __call__(self, x, decode: bool = False, max_len: int = 0):
        h = self._norm("norm_attn")(x)
        mix = self._sparse if self.mixer == "minicpm4" else self._lightning
        x = x + self.residual_scale * mix(h, decode, max_len).astype(x.dtype)
        u = self._norm("norm_mlp")(x)
        u = nn.silu(self._dense(self.intermediate, "mlp_gate")(u)) * self._dense(
            self.intermediate, "mlp_up")(u)
        return x + self.residual_scale * self._dense(self.dim, "mlp_down")(u)

    def _cursor(self):
        """(index variable, n_valid) of a decode-mode call."""
        idx = self.variable("cache", "index", _external("index"))
        n_valid = self.variable("cache", "n_valid", _external("n_valid")).value
        return idx, n_valid

    # ------------------------------------------------ block-sparse attention
    def _sparse(self, h, decode, max_len):
        b, s, _ = h.shape
        nh, hkv, d = self.heads, self.heads_kv, self.head_dim
        q = self._dense(nh * d, "q_proj")(h).reshape(b, s, nh, d)
        k = self._dense(hkv * d, "k_proj")(h).reshape(b, s, hkv, d)
        v = self._dense(hkv * d, "v_proj")(h).reshape(b, s, hkv, d)
        gate = self._dense(nh * d, "g_proj")(h)
        # float32 out of the norm: the selection scores are taken from these
        q32 = self._norm("q_norm", jnp.float32)(q)
        k32 = self._norm("k_norm", jnp.float32)(k)
        q, k = q32.astype(self.dtype), k32.astype(self.dtype)
        if not decode:
            o = self._dense_attention(q, k, v)
        elif s == 1:
            o = self._sparse_step(q, q32, k, v, max_len)
        else:
            o = self._sparse_chunk(q, q32, k, k32, v, max_len)
        o = o.reshape(b, s, nh * d) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return self._dense(self.dim, "o_proj")(o.astype(self.dtype))

    def _dense_attention(self, q, k, v):
        b, s, nh, d = q.shape
        if s > self.sparse.dense_len:
            raise ValueError(
                f"the plain forward attends densely: {s} tokens exceed "
                f"dense_len ({self.sparse.dense_len}); longer contexts go "
                "through the serving engine's caches")
        hkv = k.shape[2]
        qg = q.reshape(b, s, hkv, nh // hkv, d)
        sc = jnp.einsum("bqkgd,bnkd->bkgqn", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
        o = jnp.einsum("bkgqn,bnkd->bqkgd", jax.nn.softmax(sc, -1).astype(v.dtype),
                       v, preferred_element_type=jnp.float32)
        return o.reshape(b, s, nh, d)

    def _pool(self, max_len):
        ps, spec = self.page_size, self.sparse
        if ps != spec.block_size:
            raise ValueError(
                f"a selectable block is one page: page_size ({ps}) must "
                f"equal the model's block_size ({spec.block_size})")
        if max_len % ps:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of page_size ({ps})")
        pages_k = self.variable("cache", "pages_k", _external("pages_k"))
        pages_v = self.variable("cache", "pages_v", _external("pages_v"))
        if not paged_kernel_eligible(self.dtype, pages_k.value.dtype, ps,
                                     self.heads_kv, self.head_dim):
            raise ValueError(
                "block-sparse attention reads the pool through the paged "
                "kernels only (ops.paged_attention.paged_kernel_eligible): "
                f"pool {pages_k.value.dtype} for compute {self.dtype}, page "
                f"{ps}, {self.heads_kv} KV heads of {self.head_dim}")
        bt = self.variable("cache", "block_table", _external("block_table")).value
        kc = self.variable("cache", "kc", _external("kc"))
        return pages_k, pages_v, bt, kc

    def _sparse_step(self, q, q32, k, v, max_len):
        """One token a row, all rows: write it, complete a compressed key
        where one ends here, select, and read the selected pages."""
        spec, ps = self.sparse, self.page_size
        pages_k, pages_v, bt, kc = self._pool(max_len)
        idx_var, n_valid = self._cursor()
        b, _, nh, d = q.shape
        hkv = k.shape[2]
        live = n_valid > 0
        t = jnp.minimum(idx_var.value, max_len - 1)  # the token's position
        # a row that is not decoding writes to the trash page
        page = jnp.where(live, jnp.take_along_axis(bt, (t // ps)[:, None], 1)[:, 0], 0)
        pages_k.value = pages_k.value.at[page, t % ps].set(k[:, 0])
        pages_v.value = pages_v.value.at[page, t % ps].set(v[:, 0])
        idx_var.value = jnp.minimum(idx_var.value + n_valid, max_len)
        # the kernel whose last token is t: mean of the row's last
        # kernel_size keys, read back from its pages
        ksz, stride = spec.kernel_size, spec.kernel_stride
        pos = jnp.maximum(t[:, None] - (ksz - 1) + jnp.arange(ksz), 0)
        pg = jnp.take_along_axis(bt, pos // ps, axis=1)
        mean = pages_k.value[pg, pos % ps].astype(jnp.float32).mean(1)
        due = live & ((t + 1) % stride == 0) & (t + 1 >= ksz)
        j = jnp.where(due, (t + 1 - ksz) // stride, kc.value.shape[1])
        kc.value = kc.value.at[jnp.arange(b), j].set(mean, mode="drop")
        scores = sparse_ops.block_scores(
            q32[:, 0].reshape(b, hkv, nh // hkv, d), kc.value, t, spec)
        pages, lengths, blocks = sparse_ops.decode_page_table(scores, t, bt, spec)
        # what the kernel is handed this step, left in the cache for whoever
        # audits the selection (the benchmark's check, the tests): each
        # (row, KV head)'s block ids and its list's length in tokens
        self.variable("cache", "sel", _external("sel")).value = blocks
        self.variable("cache", "sel_len", _external("sel_len")).value = lengths
        # one kernel row per (row, KV head): the page holds both heads, the
        # row's output is read at its own head's group
        o = paged_decode_attention(
            jnp.repeat(q[:, 0], hkv, axis=0), pages_k.value, pages_v.value,
            pages.reshape(b * hkv, -1), lengths.reshape(-1))
        o = o.reshape(b, hkv, hkv, nh // hkv, d)
        o = o[:, jnp.arange(hkv), jnp.arange(hkv)]  # (B, Hkv, G, D)
        return o.reshape(b, 1, nh, d)

    def _sparse_chunk(self, q, q32, k, k32, v, max_len):
        """ONE row's prefill chunk at its cursor."""
        spec, ps = self.sparse, self.page_size
        pages_k, pages_v, bt, kc = self._pool(max_len)
        idx_var, n_valid = self._cursor()
        b, c, nh, d = q.shape
        hkv = k.shape[2]
        stride = spec.kernel_stride
        if b != 1 or c % ps:
            raise ValueError(
                f"a prefill chunk is one row of whole pages, got {b} rows of "
                f"{c} tokens (page {ps})")
        start = idx_var.value[0]
        pos = jnp.minimum(start + jnp.arange(c), max_len - 1)
        page = bt[0, pos // ps]
        pages_k.value = pages_k.value.at[page, pos % ps].set(k[0])
        pages_v.value = pages_v.value.at[page, pos % ps].set(v[0])
        idx_var.value = jnp.minimum(idx_var.value + n_valid, max_len)
        # compressed keys that end in this chunk: the first starts in the
        # stride before it (read back from the pool; none before position 0)
        prev = jnp.maximum(start - stride + jnp.arange(stride), 0)
        k_prev = pages_k.value[bt[0, prev // ps], prev % ps].astype(jnp.float32)
        new = sparse_ops.compress_keys(jnp.concatenate([k_prev, k32[0]]), spec)
        j = start // stride - 1 + jnp.arange(c // stride)
        j = jnp.where(j >= 0, j, kc.value.shape[1])
        kc.value = kc.value.at[0, j].set(new, mode="drop")
        tile = _SELECT_TILE if c % _SELECT_TILE == 0 else c

        def select(args):
            q_t, t = args
            return sparse_ops.selection_bitmap(
                sparse_ops.block_scores(q_t, kc.value[0], t, spec), t, spec)

        sel = jax.lax.map(select, (
            q32[0].reshape(c // tile, tile, hkv, nh // hkv, d),
            (start + jnp.arange(c)).reshape(c // tile, tile)))
        o = sparse_ops.sparse_prefill_attention(
            q[0], sel.reshape(c, hkv, -1), pages_k.value, pages_v.value,
            bt[0], start)
        return o[None]

    # ---------------------------------------------------- lightning attention
    def _lightning(self, h, decode, max_len):
        b, s, _ = h.shape
        nh, d = self.lightning_heads, self.head_dim
        q = self._dense(nh * d, "q_proj")(h).reshape(b, s, nh, d)
        k = self._dense(nh * d, "k_proj")(h).reshape(b, s, nh, d)
        v = self._dense(nh * d, "v_proj")(h).reshape(b, s, nh, d)
        gate = self._dense(nh * d, "g_proj")(h)
        q = self._norm("q_norm")(q)
        k = self._norm("k_norm")(k)
        slopes = lightning_slopes(nh)
        if decode:
            idx_var, n_valid = self._cursor()
            state = self.variable("cache", "state", _external("state"))
            idx = idx_var.value
            q = apply_rope(q, self.rope_theta, offset=idx)
            k = apply_rope(k, self.rope_theta, offset=idx)
            idx_var.value = jnp.minimum(idx + n_valid, max_len)
            if s == 1:
                o, state.value = lightning_step(
                    q[:, 0], k[:, 0], v[:, 0], state.value, slopes, n_valid > 0)
                o = o[:, None]
            else:
                if b != 1:
                    raise ValueError(
                        f"a prefill chunk is one row, got {b} rows of {s} tokens")
                # a row's first chunk starts from nothing, whatever the
                # slot's last tenant left in the pool
                s0 = jnp.where(idx[0] > 0, state.value[0], 0.0)
                o, s1 = lightning_chunk_scan(
                    q[0].transpose(1, 0, 2), k[0].transpose(1, 0, 2),
                    v[0].transpose(1, 0, 2), s0, slopes, n_valid[0])
                o = o.transpose(1, 0, 2)[None]
                state.value = s1[None]
        else:
            q, k = apply_rope(q, self.rope_theta), apply_rope(k, self.rope_theta)
            rows = [lightning_chunk_scan(
                q[r].transpose(1, 0, 2), k[r].transpose(1, 0, 2),
                v[r].transpose(1, 0, 2), jnp.zeros((nh, d, d), jnp.float32),
                slopes, jnp.int32(s))[0].transpose(1, 0, 2) for r in range(b)]
            o = jnp.stack(rows)
        o = self._norm("out_norm")(o.reshape(b, s, nh * d))
        o = o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return self._dense(self.dim, "o_proj")(o.astype(self.dtype))


class SalaLM(nn.Module):
    """Embed -> blocks of ``mixer_types`` -> RMSNorm -> untied head."""

    num_classes: int = 64  # vocabulary size (named for zoo consistency)
    dim: int = 128
    mixer_types: tuple = ("minicpm4", "lightning-attn", "lightning-attn",
                          "lightning-attn")
    heads: int = 4
    heads_kv: int = 2
    head_dim: int = 128
    lightning_heads: int = 4
    intermediate: int = 256
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    residual_layers: int = 0  # the depth the residual scale is taken at
    #   (the PUBLISHED one under a depth cut); 0 = this model's own
    logit_divisor: float = 1.0  # hidden_size / dim_model_base
    sparse: SparseSpec = SparseSpec()
    page_size: int = 0  # set by the serving engine on its decode clone
    paged_one_device: bool = False  # accepted for the engine's clone; the
    #   paged kernels are this model's only read path, so a mesh is refused
    #   by the engine, not here
    dtype: jnp.dtype = jnp.bfloat16

    has_recurrent_state = True  # the engine keeps a state pool for it

    @property
    def depth(self) -> int:
        return len(self.mixer_types)

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False,
                 max_len: int = 0, ragged: bool = False):
        del train, ragged  # no dropout; every row owns its cursor
        for m in self.mixer_types:
            if m not in MIXERS:
                raise ValueError(f"unknown mixer {m!r}; use one of {MIXERS}")
        if decode and not self.page_size:
            raise ValueError(
                "SalaLM decodes through the paged cache only: the engine "
                "needs kv_page_size > 0")
        x = nn.Embed(self.num_classes, self.dim, dtype=self.dtype,
                     name="embed")(tokens.astype(jnp.int32))
        x = (x.astype(jnp.float32) * self.scale_emb).astype(self.dtype)
        r = self.scale_depth / (self.residual_layers or self.depth) ** 0.5
        for i, m in enumerate(self.mixer_types):
            x = SalaBlock(
                mixer=m, dim=self.dim, heads=self.heads, heads_kv=self.heads_kv,
                head_dim=self.head_dim, lightning_heads=self.lightning_heads,
                intermediate=self.intermediate, norm_eps=self.norm_eps,
                rope_theta=self.rope_theta, residual_scale=r,
                sparse=self.sparse, page_size=self.page_size, dtype=self.dtype,
                name=f"block_{i}")(x, decode, max_len)
        x = RMSNorm(self.norm_eps, jnp.float32, name="norm_out")(x)
        x = (x / self.logit_divisor).astype(self.dtype)
        x = nn.Dense(self.num_classes, use_bias=False, dtype=self.dtype,
                     name="logits")(x)
        return x.astype(jnp.float32)

    def decode_read_plan(self, ctx):
        """What one decode step reads, for the engine's counters: ``ctx``
        (rows, steps) int contexts (position + 1) of the decoding rows at
        each step of a window.  Returns (pages the paged kernel is handed,
        pages those rows hold, row-steps that read densely), the pages summed
        over sparse layers and KV heads.  The same arithmetic as
        ``sparse_attention.decode_page_table``, on the host's record of the
        rows' lengths; the tests and the benchmark's check hold it against
        the device's own ``sel_len``."""
        spec = self.sparse
        per = self.mixer_types.count("minicpm4") * self.heads_kv
        live = (ctx - 1) // spec.block_size + 1
        dense = ctx <= spec.dense_len
        read = np.where(dense, live, spec.n_selected)
        return int(read.sum()) * per, int(live.sum()) * per, int(dense.sum())

    def paged_cache_shapes(self, slots: int, max_len: int, page_size: int,
                           n_pages: int) -> dict:
        """The decode cache's leaves per block (serving/kv_pool.py
        ``paged_cache_shapes`` asks a model that has this method): K/V pages
        and compressed keys for the sparse layers, one state per row for the
        lightning layers."""
        struct = jax.ShapeDtypeStruct
        index = struct((slots,), jnp.int32)
        out = {}
        for i, m in enumerate(self.mixer_types):
            if m == "minicpm4":
                pool = struct((n_pages, page_size, self.heads_kv, self.head_dim),
                              self.dtype)
                out[f"block_{i}"] = {
                    "pages_k": pool, "pages_v": pool,
                    "block_table": struct((slots, max_len // page_size), jnp.int32),
                    "index": index,
                    "kc": struct((slots, self.sparse.n_kernels(max_len),
                                  self.heads_kv, self.head_dim), jnp.float32),
                    "sel": struct((slots, self.heads_kv,
                                   self.sparse.table_width), jnp.int32),
                    "sel_len": struct((slots, self.heads_kv), jnp.int32),
                }
            else:
                out[f"block_{i}"] = {
                    "state": struct((slots, self.lightning_heads, self.head_dim,
                                     self.head_dim), jnp.float32),
                    "index": index,
                }
        return out
