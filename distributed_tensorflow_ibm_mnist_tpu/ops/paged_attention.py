"""Paged decode attention as a Pallas TPU kernel: one query token per row
against that row's LIVE pages of the serving engine's KV page pool.

The XLA form of paged decode (models/transformer.py
``_paged_decode_attention``) gathers ``pool[block_table]`` back to every
slot's full ``(max_len, H_kv, D)`` virtual span and scores all of it under a
mask, so a decode step costs ``slots * max_len`` positions of HBM traffic
whatever the cursors say.  This kernel walks each row's block table only as
far as the row's length:

* block table and lengths arrive as SCALAR PREFETCH (SMEM); a row of length
  ``L`` issues ``ceil(L / page_size)`` page reads and no more — entries past
  the row's pages (the trash page) are never dereferenced;
* the grid is over ROWS; the loop over a row's pages runs INSIDE the kernel,
  a wave of ``_WAVE_PAGES`` pages at a time, fetched by manual DMA into one of
  two VMEM buffers while the previous wave is scored (the next row's first
  wave is in flight while this row's last is scored, so the pipeline never
  drains between rows);
* a wave is scored whole (one (group, D) x (D, wave) product a KV head) and
  folded into the row's running online softmax — on a v5e scoring a wave in
  smaller live-only chunks lost more to loop overhead than it saved;
* one fetch of a page serves every KV head and all query heads of each GQA
  group (no repeat of K/V).

The pool is read AS STORED, ``(n_pages, page_size, H_kv, D)``: XLA lays that
out with one ``(H_kv, D)`` tile per token, which is byte-for-byte a
``(page_size * H_kv * itemsize / 4, D)`` matrix of 32-bit rows.  The kernel
views it so (a ref bitcast + reshape, no data moves, no relayout copy of the
pool), fetches whole pages, and splits heads in registers: 32-bit pools take
every ``H_kv``-th row; 16-bit pools hold heads ``2r`` and ``2r + 1`` in the
low and high half of row ``r``'s words, which a shift or a mask widens to
f32 exactly.

Arithmetic is the gather path's: compute-dtype MXU operands, f32 scores
scaled by ``D ** -0.5``, support ``position < length``, f32 softmax
statistics and accumulation.  The one difference is the order of rounding:
probabilities enter the PV product unnormalised (the division by the
softmax sum happens once, in f32, at the end) where the gather path rounds
the normalised ones — same precision, not bit-identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret

_MASK = -1e30  # the gather path's mask value (models/transformer._attend_cached)
_WAVE_PAGES = 8  # pages per DMA wave (one of two VMEM buffers).  On a v5e,
#   64 rows of 256-1536 tokens: 0.161 ms a call at 8, 0.154 at 16; 4 long
#   rows beside 60 idle ones: 0.100 at 8, 0.128 at 16 (an idle row still
#   scores a whole wave)
_LANES = 128
_SUBLANES = 8


def paged_kernel_eligible(compute_dtype, pool_dtype, page_size: int,
                          hkv: int, d: int, dv: int | None = None) -> bool:
    """Whether :func:`paged_decode_attention` can read a pool of this shape:
    the pool stores the compute dtype (an int8 pool with scales beside it
    does not), a K row (``d``) and a V row (``dv``, ``d`` when not named)
    are each a whole number of lanes and a page a whole number of sublane
    tiles (the benchmark's 128 / 128 and 256 / 128, pages of 64), and 16-bit
    pools pair their KV heads into 32-bit words.  A token's heads must fill
    1, 2 or 4 32-bit rows: XLA pads any other count to the next tile, and
    the page is then no longer the dense matrix of rows the kernel takes it
    for.  Static — shapes and dtypes only."""
    pool_dtype = jnp.dtype(pool_dtype)
    if pool_dtype != jnp.dtype(compute_dtype):
        return False
    if pool_dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    pack = 4 // pool_dtype.itemsize
    dv = d if dv is None else dv
    return (d > 0 and dv > 0 and d % _LANES == 0 and dv % _LANES == 0
            and page_size % _SUBLANES == 0
            and hkv % pack == 0 and hkv // pack in (1, 2, 4))


def _kernel(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            slot_ref, *, n_rows, n_row, ps, hkv, pack, dk, dv, scale, cdtype):
    b = pl.program_id(0)
    wave = _WAVE_PAGES
    rpt = hkv // pack  # 32-bit rows per token (pack = KV heads per word)
    rows = ps * rpt
    if pack == 2:
        k_src = k_hbm.bitcast(jnp.uint32).reshape(k_hbm.shape[0], rows, dk)
        v_src = v_hbm.bitcast(jnp.uint32).reshape(v_hbm.shape[0], rows, dv)
    else:
        k_src = k_hbm.reshape(k_hbm.shape[0], rows, dk)
        v_src = v_hbm.reshape(v_hbm.shape[0], rows, dv)

    def n_pages_of(row):
        return (len_ref[row] + ps - 1) // ps

    def page_copies(src, buf, pid, slot, j, d, sem, act):
        # a page wider than the lanes lands as one (rows, 128) block per
        # 128 lanes (the buffer's extra axis): Mosaic's strided loads, which
        # split the heads below, take 128-lane rows only
        if d == _LANES:
            act(pltpu.make_async_copy(src.at[pid], buf.at[slot, j], sem))
        else:
            for c in range(d // _LANES):
                act(pltpu.make_async_copy(
                    src.at[pid, :, pl.ds(c * _LANES, _LANES)],
                    buf.at[slot, j, c], sem))

    def wave_copies(row, w, slot, act):
        # the wave's pages that exist: one K and one V DMA each
        npg = n_pages_of(row)
        for j in range(wave):
            @pl.when(w * wave + j < npg)
            def _():
                pid = bt_ref[row * n_row + w * wave + j]
                page_copies(k_src, kbuf, pid, slot, j, dk, sem.at[0, slot], act)
                page_copies(v_src, vbuf, pid, slot, j, dv, sem.at[1, slot], act)

    @pl.when(b == 0)
    def _():
        # buffers start finite: a wave's unfetched tail is multiplied by
        # probabilities that are exactly 0, and 0 * NaN is NaN
        slot_ref[0] = 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        wave_copies(0, 0, 0, lambda c: c.start())

    length = len_ref[b]
    npg = n_pages_of(b)
    n_waves = (npg + wave - 1) // wave
    tw = wave * ps  # positions per wave

    def heads_of(buf, slot, d):
        """The wave's (tw, d) operand per KV head, from 32-bit rows."""
        out = []
        for r in range(rpt):
            if d != _LANES:
                x = jnp.concatenate([
                    buf[slot, :, pl.ds(c, 1), pl.ds(r, ps, stride=rpt), :]
                    .reshape(tw, _LANES) for c in range(d // _LANES)], axis=-1)
            elif rpt == 1:
                x = buf[slot].reshape(tw, d)
            else:
                x = buf[slot, :, pl.ds(r, ps, stride=rpt), :].reshape(tw, d)
            if pack == 1:
                out.append(x)
            else:
                out.append(lax.bitcast_convert_type(
                    x << 16, jnp.float32).astype(cdtype))
                out.append(lax.bitcast_convert_type(
                    x & jnp.uint32(0xFFFF0000), jnp.float32).astype(cdtype))
        return out

    def wave_body(w, carry):
        slot = slot_ref[0]
        last = w + 1 >= n_waves
        nxt_row = jnp.where(last, b + 1, b)
        nxt_w = jnp.where(last, 0, w + 1)

        @pl.when(nxt_row < n_rows)
        def _():
            wave_copies(nxt_row, nxt_w, 1 - slot, lambda c: c.start())

        wave_copies(b, w, slot, lambda c: c.wait())
        pos = w * tw + lax.broadcasted_iota(jnp.int32, (1, tw), 1)
        valid = pos < length
        ks, vs = heads_of(kbuf, slot, dk), heads_of(vbuf, slot, dv)
        out = []
        for h in range(hkv):
            m, l, acc = carry[h]
            s = lax.dot_general(
                q_ref[h], ks[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, _MASK)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + lax.dot_general(
                p.astype(cdtype), vs[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l, acc))
        slot_ref[0] = 1 - slot
        return tuple(out)

    g = q_ref.shape[1]
    init = tuple(
        (jnp.full((g, 1), -jnp.inf, jnp.float32),
         jnp.zeros((g, 1), jnp.float32),
         jnp.zeros((g, dv), jnp.float32)) for _ in range(hkv))
    fin = lax.fori_loop(0, n_waves, wave_body, init)
    for h in range(hkv):
        _, l, acc = fin[h]
        o_ref[h] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_decode_attention(q, pages_k, pages_v, block_table, lengths,
                            scale, interpret):
    b, h, dk = q.shape
    n_pages, ps, hkv, _ = pages_k.shape
    dv = pages_v.shape[-1]
    n_row = block_table.shape[1]
    g = h // hkv
    # query rows of a group padded to a whole sublane tile of the compute
    # dtype (12 -> 16): the pad rows score garbage nobody reads
    tile = _SUBLANES * 4 // jnp.dtype(q.dtype).itemsize
    gp = -(-g // tile) * tile
    qg = jnp.pad(q.reshape(b, hkv, g, dk),
                 ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    pack = 4 // pages_k.dtype.itemsize  # KV heads per 32-bit word

    def buf(d):
        rows = ps * hkv // pack
        return pltpu.VMEM(
            (2, _WAVE_PAGES, rows, d) if d == _LANES else
            (2, _WAVE_PAGES, d // _LANES, rows, _LANES),
            jnp.uint32 if pack == 2 else pages_k.dtype)

    def spec(d):
        return pl.BlockSpec((None, hkv, gp, d), lambda i, *_: (i, 0, 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_rows=b, n_row=n_row, ps=ps, hkv=hkv, pack=pack,
            dk=dk, dv=dv, scale=dk ** -0.5 if scale is None else scale,
            cdtype=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[spec(dk), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec(dv),
            scratch_shapes=[buf(dk), buf(dv),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, dv), q.dtype),
        name="paged_decode_attention",
        **({"interpret": True} if interpret else {
            "interpret": False,
            # rows run in order: each prefetches the next one's first wave
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("arbitrary",))}),
    )(jnp.clip(lengths, 1, n_row * ps).astype(jnp.int32),
      block_table.reshape(-1).astype(jnp.int32), qg, pages_k, pages_v)
    return out[:, :, :g].reshape(b, h, dv)


def paged_decode_attention(q, pages_k, pages_v, block_table, lengths,
                           scale: float | None = None,
                           interpret: bool | None = None):
    """One decode step of attention over a paged KV pool.

    ``q`` (B, H, Dk) in the compute dtype; ``pages_k`` (n_pages, page_size,
    H_kv, Dk) and ``pages_v`` (n_pages, page_size, H_kv, Dv) pools of the
    same dtype, the current token's K/V already written; ``block_table``
    (B, max_len // page_size) page ids; ``lengths`` (B,) positions each row
    attends, clamped to ``[1, max_len]``.  Returns (B, H, Dv).  Scores are
    scaled by ``scale`` (``Dk ** -0.5`` when not named: a model that stores
    its keys zero-padded to whole lanes names the scale of the width it
    computes with).  Shapes must satisfy :func:`paged_kernel_eligible`.

    The body is one ``jax.jit``-ed function: thirty layers calling it with
    identical avals trace and lower the kernel once.
    """
    return _paged_decode_attention(
        q, pages_k, pages_v, block_table, lengths, scale=scale,
        interpret=resolve_interpret(interpret))
