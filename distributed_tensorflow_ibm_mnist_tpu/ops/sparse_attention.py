"""Block-sparse attention over the paged KV pool (InfLLM-v2 selection): the
selection arithmetic, and the Pallas TPU kernel a prefill chunk attends
through.

A query at position ``t`` whose context ``t + 1`` exceeds ``dense_len``
attends to ``init_blocks`` leading blocks, the ``window_size / block_size``
blocks that end at its own, and the ``topk`` best of the blocks between, one
choice per KV head (:class:`SparseSpec`).  A block is one page of the pool.
Blocks are ranked from COMPRESSED keys, ``Kc_j = mean(k[stride * j :
stride * j + kernel_size])``: over the kernels wholly in the query's past,
``softmax_j(q_h . Kc_j / sqrt(D))`` summed over the query heads of the KV
group, and a block's score is the largest over the kernels that overlap it.
All of that is float32 at ``HIGHEST`` precision (:func:`block_scores`,
:func:`select_blocks`): with NoPE the scores are near-ties, and a selection
made in bf16 is another selection.

Decode needs nothing new on the device: the selection of a (row, KV head)
is a page list, which the paged decode kernel (``ops/paged_attention.py``)
already takes (:func:`decode_page_table`).

A prefill chunk's queries each have their own selection, so the chunk goes
through :func:`sparse_prefill_attention`: a tile of queries against the
row's own pages, fetched a wave at a time through the row's block table as
the decode kernel does, masked per (query, block) by the selection bitmap
and per (query, key) by causality, online softmax.  It walks every page up
to the tile's last query — a page that no query of the tile selected is
fetched and masked, not skipped — so it computes more than the selection
needs, never less.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret

_HI = lax.Precision.HIGHEST
_MASK = -1e30
_WAVE_PAGES = 8   # pages per DMA wave, as the decode kernel's
_LANES = 128


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The sizes of one model's block selection (MiniCPM4's ``sparse_config``)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError(
                "compressed keys are kept as means of two strides: "
                f"kernel_size ({self.kernel_size}) must be twice "
                f"kernel_stride ({self.kernel_stride})")
        if self.block_size % self.kernel_stride or self.window_size % self.block_size:
            raise ValueError(
                f"block_size ({self.block_size}) must be a multiple of "
                f"kernel_stride ({self.kernel_stride}) and window_size "
                f"({self.window_size}) of block_size")
        if self.dense_len % self.block_size or (
                self.dense_len // self.block_size - self.local_blocks
                - self.init_blocks + 1 < self.topk):
            raise ValueError(
                f"dense_len ({self.dense_len}) must be whole blocks, and the "
                f"first query past it must find topk ({self.topk}) blocks "
                f"between its first {self.init_blocks} and its last "
                f"{self.local_blocks}")

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def n_selected(self) -> int:
        """Blocks a sparse query reads."""
        return self.init_blocks + self.topk + self.local_blocks

    @property
    def table_width(self) -> int:
        """Width of a decode page list: a dense row's pages or a selection."""
        return max(self.dense_len // self.block_size, self.n_selected)

    def n_kernels(self, max_len: int) -> int:
        return max_len // self.kernel_stride


def compress_keys(k_ext, spec: SparseSpec):
    """Compressed keys of a run of keys.  ``k_ext`` (stride + T, ...) float32
    holds the ``kernel_stride`` keys before the run and then the run (``T`` a
    multiple of the stride); returns the ``T / stride`` kernels whose LAST
    token lies in the run, oldest first (the first one starts in the keys
    before the run)."""
    s = spec.kernel_stride
    seg = k_ext.reshape((k_ext.shape[0] // s, s) + k_ext.shape[1:]).mean(1)
    return 0.5 * (seg[:-1] + seg[1:])


def block_scores(q, kc, t, spec: SparseSpec):
    """Scores of every block for queries at positions ``t``.

    ``q`` (..., Hkv, G, D) float32 queries (one position each), ``kc``
    (..., NK, Hkv, D) float32 compressed keys of the queries' row, ``t``
    (...) int32.  Returns (..., Hkv, NK * stride / block) float32; a block
    none of whose kernels lies wholly in the query's past scores -1."""
    d = q.shape[-1]
    nk = kc.shape[-3]
    s = jnp.einsum("...kgd,...jkd->...kgj", q, kc, precision=_HI) * d ** -0.5
    # kernel j covers [stride * j, stride * j + kernel_size): wholly past
    # at query t when its last token is at or before t
    seen = (jnp.arange(nk) * spec.kernel_stride + spec.kernel_size - 1
            <= t[..., None])[..., None, None, :]
    p = jax.nn.softmax(jnp.where(seen, s, _MASK), axis=-1)
    p = jnp.where(seen[..., 0, :], p.sum(-2), -1.0)  # (..., Hkv, NK)
    r = spec.block_size // spec.kernel_stride
    e = spec.kernel_size // spec.kernel_stride - 1
    pad = [(0, 0)] * (p.ndim - 1) + [(e, 0)]
    p = jnp.pad(p, pad, constant_values=-1.0)
    # block b overlaps kernels r*b - e .. r*b + r - 1
    return functools.reduce(
        jnp.maximum, [p[..., o:o + nk:r] for o in range(r + e)])


def select_blocks(scores, t, spec: SparseSpec):
    """The ``topk`` block ids (ascending) of each (query, KV head) among
    the blocks between the first ``init_blocks`` and the window.  ``scores``
    (..., Hkv, NB), ``t`` (...)."""
    nb = scores.shape[-1]
    b = jnp.arange(nb)
    last = (t // spec.block_size)[..., None, None]
    cand = (b >= spec.init_blocks) & (b <= last - spec.local_blocks)
    _, idx = lax.top_k(jnp.where(cand, scores, -jnp.inf), spec.topk)
    return jnp.sort(idx, axis=-1)


def decode_page_table(scores, t, block_table, spec: SparseSpec):
    """The page list and length the paged decode kernel reads for each
    (row, KV head) of a decode step.

    ``scores`` (B, Hkv, NB), ``t`` (B,) the position of the step's token,
    ``block_table`` (B, n_row).  A row within ``dense_len`` reads its pages
    in order; a longer one its selection, ascending, so that the one partial
    page (the token's own) is the list's last.  Returns ``(pages (B, Hkv, W),
    lengths (B, Hkv), blocks (B, Hkv, W))``."""
    ps, w = spec.block_size, spec.table_width
    hkv = scores.shape[1]
    last = t // ps
    top = select_blocks(scores, t, spec)  # (B, Hkv, topk)
    shape = top.shape[:-1]
    init = jnp.broadcast_to(jnp.arange(spec.init_blocks), shape + (spec.init_blocks,))
    local = (last[:, None, None] - (spec.local_blocks - 1)
             + jnp.broadcast_to(jnp.arange(spec.local_blocks),
                                shape + (spec.local_blocks,)))
    sel = jnp.concatenate([init, top, local], axis=-1)
    sel = jnp.pad(sel, [(0, 0), (0, 0), (0, w - spec.n_selected)])
    sparse = (t + 1 > spec.dense_len)[:, None]
    blocks = jnp.where(sparse[..., None], sel, jnp.arange(w))
    blocks = jnp.clip(blocks, 0, block_table.shape[1] - 1)
    lengths = jnp.where(
        sparse, (spec.n_selected - 1) * ps + (t % ps)[:, None] + 1,
        (t + 1)[:, None])
    lengths = jnp.broadcast_to(lengths, (t.shape[0], hkv))
    pages = jnp.take_along_axis(block_table[:, None, :], blocks, axis=-1)
    return pages, lengths, blocks


def _kth_largest_bits(bits, k: int):
    """The largest ``T`` with at least ``k`` entries of non-negative int32
    ``bits`` at or above it, along the last axis: the k-th largest entry, by
    bisection over the 31 value bits (31 counting passes; a sort of the same
    rows costs the chip some twenty times as much)."""
    lo = jnp.zeros(bits.shape[:-1], jnp.int32)
    hi = jnp.full(bits.shape[:-1], 0x7F800000, jnp.int32)  # +inf's bits

    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + (hi - lo + 1) // 2
        enough = (bits >= mid[..., None]).sum(-1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    return lax.fori_loop(0, 31, halve, (lo, hi))[0]


def selection_bitmap(scores, t, spec: SparseSpec):
    """Which blocks each (query, KV head) attends to, as 0/1 over all
    blocks: ``scores`` (T, Hkv, NB), ``t`` (T,) -> (T, Hkv, NB) bool.  The
    same set :func:`select_blocks` names, found without sorting: a bitmap
    needs the ``topk``-th largest score as a threshold, not the order (a
    non-negative float32 orders as its bit pattern does).  Ties are the
    rule, not the exception — neighbouring blocks share the kernel that
    straddles their border, and where it is both blocks' best they score
    the same — so of the blocks AT the threshold the lowest ids are taken,
    as ``lax.top_k`` and a stable sort take them."""
    nb = scores.shape[-1]
    b = jnp.arange(nb)
    last = (t // spec.block_size)[:, None, None]
    cand = (b >= spec.init_blocks) & (b <= last - spec.local_blocks)
    # a candidate's score is a sum of softmax probabilities, above zero; -1
    # marks a block with no kernel in the query's past: zero here, never taken
    bits = lax.bitcast_convert_type(
        jnp.where(cand, jnp.maximum(scores, 0.0), 0.0), jnp.int32)
    kth = jnp.maximum(_kth_largest_bits(bits, spec.topk), 1)[..., None]
    above, at = bits > kth, bits == kth
    room = spec.topk - above.sum(-1, keepdims=True)
    picked = cand & (above | (at & (jnp.cumsum(at, axis=-1) <= room)))
    sparse = picked | (b < spec.init_blocks) | (
        (b > last - spec.local_blocks) & (b <= last))
    return jnp.where((t + 1 > spec.dense_len)[:, None, None], sparse, b <= last)


# ---------------------------------------------------------------- the kernel


def _prefill_kernel(start_ref, bt_ref, q_ref, sel_ref, k_hbm, v_hbm, o_ref,
                    kbuf, vbuf, sem, m_sc, l_sc, acc_sc, *, tq, g, ps, hkv,
                    pack, d, scale, cdtype):
    i = pl.program_id(0)
    wave = _WAVE_PAGES
    rpt = hkv // pack
    rows = ps * rpt
    tw = wave * ps
    if pack == 2:
        k_src = k_hbm.bitcast(jnp.uint32).reshape(k_hbm.shape[0], rows, d)
        v_src = v_hbm.bitcast(jnp.uint32).reshape(v_hbm.shape[0], rows, d)
    else:
        k_src = k_hbm.reshape(k_hbm.shape[0], rows, d)
        v_src = v_hbm.reshape(v_hbm.shape[0], rows, d)
    q0 = start_ref[0] + i * tq          # position of the tile's first query
    npg = (q0 + tq + ps - 1) // ps      # pages up to the tile's last query
    n_waves = (npg + wave - 1) // wave

    def wave_copies(w, slot, act):
        for j in range(wave):
            @pl.when(w * wave + j < npg)
            def _():
                pid = bt_ref[w * wave + j]
                act(pltpu.make_async_copy(
                    k_src.at[pid], kbuf.at[slot, j], sem.at[0, slot]))
                act(pltpu.make_async_copy(
                    v_src.at[pid], vbuf.at[slot, j], sem.at[1, slot]))

    @pl.when(i == 0)
    def _():
        # a wave's unfetched tail is multiplied by probabilities that are
        # exactly 0, and 0 * NaN is NaN: the buffers start finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    wave_copies(0, 0, lambda c: c.start())

    def heads_of(buf, slot):
        out = []
        for r in range(rpt):
            if rpt == 1:
                x = buf[slot]
            else:
                x = buf[slot, :, pl.ds(r, ps, stride=rpt), :]
            x = x.reshape(tw, d)
            if pack == 1:
                out.append(x)
            else:
                out.append(lax.bitcast_convert_type(
                    x << 16, jnp.float32).astype(cdtype))
                out.append(lax.bitcast_convert_type(
                    x & jnp.uint32(0xFFFF0000), jnp.float32).astype(cdtype))
        return out

    def wave_body(w, carry):
        slot = lax.rem(w, 2)

        @pl.when(w + 1 < n_waves)
        def _():
            wave_copies(w + 1, 1 - slot, lambda c: c.start())

        wave_copies(w, slot, lambda c: c.wait())
        ks, vs = heads_of(kbuf, slot), heads_of(vbuf, slot)
        key = w * tw + lax.broadcasted_iota(jnp.int32, (tq, tw), 1)
        causal = key <= q0 + lax.broadcasted_iota(jnp.int32, (tq, tw), 0)
        # the wave's blocks lie in one 128-lane group of the bitmap; a
        # 0/1 product against E[b, c] = (b == block of key c) widens the
        # group's bits from blocks to keys
        grp = (w * wave) // _LANES
        blk = lax.broadcasted_iota(jnp.int32, (_LANES, tw), 0)
        col = (w * wave - grp * _LANES
               + lax.broadcasted_iota(jnp.int32, (_LANES, tw), 1) // ps)
        expand = (blk == col).astype(sel_ref.dtype)
        for h in range(hkv):
            bits = lax.dot_general(
                sel_ref[h, grp], expand, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            mask = (bits > 0.5) & causal  # (tq, tw)
            s = lax.dot_general(
                q_ref[h], ks[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask[None], s.reshape(g, tq, tw), _MASK)
            s = s.reshape(g * tq, tw)
            m = m_sc[h]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_sc[h] = alpha * l_sc[h] + p.sum(axis=-1, keepdims=True)
            acc_sc[h] = alpha * acc_sc[h] + lax.dot_general(
                p.astype(cdtype), vs[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new
        return carry

    lax.fori_loop(0, n_waves, wave_body, 0)
    for h in range(hkv):
        o_ref[h] = (acc_sc[h] / l_sc[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_prefill_attention(q, sel, pages_k, pages_v, bt_row, start,
                              interpret):
    c, h, d = q.shape
    _, ps, hkv, _ = pages_k.shape
    g = h // hkv
    tq = 64 if c % 64 == 0 else c
    n_tiles = c // tq
    nb = sel.shape[-1]
    n_grp = -(-nb // _LANES)
    # (C, H, D) -> (tiles, Hkv, G * tq, D), a tile's rows ordered (g, query)
    qt = q.reshape(n_tiles, tq, hkv, g, d).transpose(0, 2, 3, 1, 4).reshape(
        n_tiles, hkv, g * tq, d)
    # (C, Hkv, NB) bool -> (Hkv, groups, C, 128) 0/1 in the compute dtype
    bits = jnp.pad(sel, [(0, 0), (0, 0), (0, n_grp * _LANES - nb)])
    bits = bits.reshape(c, hkv, n_grp, _LANES).transpose(1, 2, 0, 3).astype(q.dtype)
    pack = 4 // pages_k.dtype.itemsize
    buf = pltpu.VMEM((2, _WAVE_PAGES, ps * hkv // pack, d),
                     jnp.uint32 if pack == 2 else pages_k.dtype)
    q_spec = pl.BlockSpec((None, hkv, g * tq, d), lambda i, *_: (i, 0, 0, 0))
    stat = pltpu.VMEM((hkv, g * tq, 1), jnp.float32)
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, tq=tq, g=g, ps=ps, hkv=hkv, pack=pack, d=d,
            scale=d ** -0.5, cdtype=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[q_spec,
                      pl.BlockSpec((hkv, n_grp, tq, _LANES),
                                   lambda i, *_: (0, 0, i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            stat, stat,
                            pltpu.VMEM((hkv, g * tq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_tiles, hkv, g * tq, d), q.dtype),
        name="sparse_prefill_attention",
        **({"interpret": True} if interpret else {
            "interpret": False,
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 1024 * 1024)}),
    )(jnp.reshape(start, (1,)).astype(jnp.int32), bt_row.astype(jnp.int32),
      qt, bits, pages_k, pages_v)
    return out.reshape(n_tiles, hkv, g, tq, d).transpose(0, 3, 1, 2, 4).reshape(
        c, h, d)


def sparse_prefill_attention(q, sel, pages_k, pages_v, bt_row, start,
                             interpret: bool | None = None):
    """A prefill chunk's attention over ONE row's pages under a per-query
    block selection.

    ``q`` (C, H, D) queries at positions ``start .. start + C`` in the
    compute dtype; ``sel`` (C, Hkv, NB) bool, the blocks each (query, KV
    head) attends to (:func:`selection_bitmap`; it must hold block 0 for
    every query so that no softmax is empty); ``pages_k``/``pages_v``
    (n_pages, page_size, Hkv, D) pools with the chunk's own keys and values
    already written; ``bt_row`` (n_row,) the row's block table.  Within a
    selected block a query attends causally.  Returns (C, H, D).  The pool's
    shape must satisfy ``ops.paged_attention.paged_kernel_eligible``."""
    return _sparse_prefill_attention(q, sel, pages_k, pages_v, bt_row, start,
                                     interpret=resolve_interpret(interpret))
