"""Pallas TPU kernel: flash attention (fwd + custom VJP bwd).

The transformer family's hot op (models/transformer.py), as a blockwise
VMEM-resident kernel.  The grid is 3-D — ``(batch*head, out-tile,
reduce-tile)`` with the reduction axis innermost and marked "arbitrary" —
so only single (tile x head_dim) blocks of Q/K/V/dO are ever resident in
VMEM while online-softmax (fwd) / recompute (bwd) accumulators live in
VMEM scratch across the innermost grid steps.  The (S x S) score matrix
never exists in HBM and VMEM stays O(tile), so sequence length scales to
HBM capacity (vs the O(S) VMEM of a whole-row design that tops out around
S~4k on v5e).  The backward pass is the standard flash recompute scheme —
probabilities rebuilt blockwise from the saved row logsumexp — fused into
ONE grid walk producing dQ, dK and dV together when dQ's full-row VMEM
accumulator fits (the round-4 rewrite; the profile priced the old
two-kernel scheme's double scores/p/ds recompute at 75% of attention
time), with the two-kernel scheme (dK/dV over q-tiles, then dQ over
k-tiles) as the long-row fallback.

MXU dtype policy (the round-3 rewrite; VERDICT.md r2 item 1): every
matmul runs with the INPUT dtype on the MXU and float32 accumulation
(``preferred_element_type``).  bf16 inputs therefore stream through the
MXU at the bf16 rate — the round-2 kernel upcast everything to f32 first,
which runs the MXU at a fraction of peak and was the dominant cost
(measured on v5e, B=4 S=8192 H=8 D=64 causal: 225 ms fwd+bwd in f32-matmul
form vs ~3x faster with native-dtype matmuls).  Softmax statistics, the
probability matrix, and all scratch accumulators stay f32; probabilities
and d(scores) are cast back to the input dtype only as MXU operands.
f32 inputs keep full-f32 matmuls, so the CPU test suite's tight
tolerances vs the dense reference are unchanged.

Layout is (B, S, H, D) like the rest of the framework; head_dim is taken
UNPADDED into the block shapes (Mosaic handles sub-128 minor dims in
registers).  The round-2 kernel zero-padded D to the 128-lane tile in HBM,
which doubled (D=64) or quadrupled (D=32) the DMA traffic and VMEM
footprint of every block on the zoo's own head sizes; the MXU's physical
128-lane contraction can't be filled by a D=64 per-head contraction from
SEPARATE heads (any lane- or sublane-packing of two heads' Q/K either sums
their score matrices or multiplies against structural zeros — same MXU
occupancy, more memory traffic), so the fix is to stop paying for the pad
in memory and bandwidth rather than to fake a fuller contraction.
Sequence padding is masked inside the kernels, so any S works.  The CPU
test suite exercises the same code path through the Pallas interpreter,
chosen explicitly (ops/interpret.py) — never guessed from the backend.

Composes with sequence parallelism: ring attention
(parallel/ring_attention.py) rotates K/V shards BETWEEN devices while this
kernel is the natural per-shard block computation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret

_NEG = -1e30
_LOG2E = 1.4426950408889634  # exp(x) == exp2(x * log2(e)): the kernels run
#   the online softmax in BASE 2 — the multiply folds into the score scale
#   (one constant fold instead of one VPU multiply per element next to the
#   EUP exponential), and lse converts back to natural log at finalize so
#   the fwd/bwd contract (p = exp(scores - lse)) is unchanged.
_LN2 = 0.6931471805599453

# Default VMEM tile sizes (q rows x k cols per inner step).  Swept on the
# v5e at B=4 S=8192 H=8 D=64 causal bf16 (round 5's microbenchmark): larger
# tiles amortize the scratch read-modify-write of the online-softmax state
# and per-step DMA setup — fwd+bwd walks 251 ms (128x128) -> 91.6
# (256x512) -> 62.5 (512x1024), then plateaus (1024x1024: 68.0, 512x2048:
# 69.5; the f32 softmax VPU work is the bottleneck once tiles are this
# big).  512x1024 keeps the (Bq x Bk) f32 score tile at 2 MB, comfortably
# inside the 16 MB scoped-VMEM budget with double-buffered operands.
_BLOCK_Q = 512
_BLOCK_K = 1024

# Forward-only tile overrides (None = use _BLOCK_Q/_BLOCK_K).  With the
# backward fused (one walk), the forward's online-softmax scratch updates
# are the next cost center, and its VMEM budget differs from the
# backward's (no dq row buffer, fewer operands) — so its tiles sweep
# independently.  Swept on the v5e at B=4 S=8192 H=8 D=64 causal bf16:
# 1024x1024 walks 16.65 ms vs 17.40 at the backward's 512x1024 (fewer
# online-softmax scratch read-modify-writes per row); 2048-row tiles
# fail to compile (VMEM), wider k-tiles are neutral-to-worse.
_FWD_BLOCK_Q = 1024
_FWD_BLOCK_K = 1024

# Fused-backward gate: the one-walk backward keeps dQ's whole (padded) row
# in VMEM — an f32 accumulator plus the output block in the input dtype,
# S_pad * D * (4 + itemsize) bytes.  4 MB leaves ~12 MB of the 16 MB
# scoped-VMEM budget for the double-buffered tile operands and the f32
# score/p/ds intermediates at the default 512x1024 tiles: S=8192 D=64
# bf16 needs 3 MB and compiles at ~11 MB scoped; S=16384 needs 6.3 MB
# and was MEASURED to blow the scoped limit (20.5 MB requested — the
# row buffer plus the intermediates don't co-fit), so rows past the
# 4 MB line take the GROUPED fused path below (round 5; previously the
# two-kernel fallback).
_FUSED_DQ_VMEM_BUDGET = 4 * 1024 * 1024

# Long rows past the gate use the GROUPED fused backward (round 5): the
# q rows are split into VMEM-sized groups, each walking all k-tiles, with
# per-group partial dK/dV summed outside the kernel.  False falls back to
# the round-3 two-kernel scheme (kept for A/B and as the escape hatch).
_GROUPED_BWD = True

# The grouped path's dq group budget is SMALLER than the fused gate: its
# f32 partial dK/dV output blocks cost ~1 MB of scoped VMEM the fused
# layout's bf16 outputs don't — measured: sizing groups against the full
# 4 MB budget requested 16.93 MB of the 16 MB scoped limit at S=16384
# (956 KB over), so the group sizing budget drops to 2.5 MB, which the
# chip accepts with headroom.
_GROUPED_DQ_VMEM_BUDGET = int(2.5 * 1024 * 1024)

# Group-count ceiling for the grouped backward.  The group sizing walks
# n_qg down to a divisor of n_q; a tile count with no divisor under the
# VMEM budget (e.g. prime n_q) would collapse n_qg to 1 and emit n_q
# full-length f32 partial dK/dV buffers — 2 x (bh, n_q, sp, d) transient
# HBM that can dwarf the model at long S (ADVICE.md r5).  Past this many
# groups the partial-buffer cost outweighs the one-recompute win, so the
# kernel falls back to the two-kernel scheme instead.
_GROUPED_MAX_GROUPS = 8


def _pick_block(n: int, target: int) -> int:
    """Largest power-of-two tile <= target dividing n (after padding, n is
    a multiple of 8, so this always lands on >= 8... or n itself if tiny)."""
    b = 8
    while b * 2 <= target and n % (b * 2) == 0:
        b *= 2
    return b if n % b == 0 else n


def _dot(a, b, dims):
    """MXU matmul in the operands' dtype with f32 accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# Interior-tile mask elision (round 5): when False, every live tile runs
# the masked body — the pre-round-5 behavior, kept togglable so one
# session on the chip can A/B the split.
_SPLIT_INTERIOR = True


def _run_tiles(causal, qi, ki, block_q, block_k, compute, window=0,
               pad_ok=True):
    """Dispatch each grid step to the right body: skip dead tiles, and run
    INTERIOR tiles — tiles whose mask would be all-true — through the
    mask-free body (round 5: the iota/compare/select chain on a
    (Bq, Bk) tile runs only where the tile actually crosses the causal
    diagonal / window edge / sequence padding.  Measured a WASH at the
    1024-tile S=8192 causal headline shape — Mosaic evidently prices the
    mask chain below timing noise there — and kept because it is free,
    reads as documentation of which tiles need masking, and bounds the
    mask cost at small tiles; see docs/PERFORMANCE.md round-5 notes).

    ``compute`` is called as ``compute(masked=...)`` with a PYTHON bool —
    the kernel builds its mask only in the boundary instantiation.
    ``pad_ok`` is the caller's this-tile-needs-no-padding-mask condition:
    ``True`` (static) when the sequence is unpadded, else a traced
    per-step bool.

    Liveness MUST mirror the clamp formulas in _kv_spec/_q_side_spec: a
    dead step's operand refs point at a live tile (so Pallas skips the
    DMA), and this gate skips the compute that would otherwise read that
    stale block."""
    if causal:
        live = (qi + 1) * block_q > ki * block_k
        below = (ki + 1) * block_k <= qi * block_q
        if window:
            live &= (ki + 1) * block_k + window - 2 >= qi * block_q
            # fully inside the window: the tile's SMALLEST k position is
            # within reach of its LARGEST q position
            below &= ki * block_k >= qi * block_q + block_q - window
        if not _SPLIT_INTERIOR:
            @pl.when(live)
            def _legacy():
                compute(masked=True)
            return
        interior = below if pad_ok is True else below & pad_ok

        @pl.when(live & interior)
        def _interior():
            compute(masked=False)

        @pl.when(live & jnp.logical_not(interior))
        def _boundary():
            compute(masked=True)
    elif pad_ok is True and _SPLIT_INTERIOR:
        compute(masked=False)
    elif not _SPLIT_INTERIOR:
        compute(masked=True)
    else:
        @pl.when(pad_ok)
        def _interior():
            compute(masked=False)

        @pl.when(jnp.logical_not(pad_ok))
        def _boundary():
            compute(masked=True)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, sm_scale, block_q, block_k, n_k, s_real, causal, window):
    # grid (bh, q-tile, k-tile), k innermost; scratch carries the online
    # softmax state (m, l, acc) across k-tiles of one q-tile.
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _compute(masked):
        q = q_ref[0]  # (Bq, D), input dtype
        k = k_ref[0]  # (Bk, D)
        v = v_ref[0]
        tq, bk = q.shape[0], k.shape[0]
        # base-2 online softmax: log2(e) folded into the score scale
        scores = _dot(q, k, (((1,), (1,)))) * (sm_scale * _LOG2E)
        if masked:  # boundary tiles only — interior masks are all-true
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (tq, bk), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (tq, bk), 1)
            mask = k_pos < s_real
            if causal:
                mask = mask & (k_pos <= q_pos)
                if window:
                    mask = mask & (k_pos > q_pos - window)
            scores = jnp.where(mask, scores, _NEG)

        m_prev, l_prev, acc_prev = m_sc[...], l_sc[...], acc_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp2(scores - m_new)
        corr = jnp.exp2(m_prev - m_new)
        m_sc[...] = m_new
        l_sc[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_prev * corr + _dot(p.astype(v.dtype), v, ((1,), (0,)))

    # Causal tile-skip, round-3 form: dead above-diagonal steps are gated
    # out AND their K/V index maps are clamped to the previous live tile
    # (see _flash_fwd), so Pallas sees an unchanged block index and issues
    # NO DMA — the round-2 rejection (860 ms gated vs 720 ms ungated)
    # gated the body but left the BlockSpec walking dead tiles, paying the
    # copies anyway.  Dead steps now cost only grid-step overhead; interior
    # steps skip the mask build entirely (round 5).
    pad_ok = True if s_real == n_k * block_k else (ki + 1) * block_k <= s_real
    _run_tiles(causal, qi, ki, block_q, block_k, _compute, window, pad_ok)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padding) rows
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        # m is in base-2 units; lse stays NATURAL log (the bwd contract)
        lse_ref[0] = m_sc[...] * _LN2 + jnp.log(l_safe)


def _bwd_tile_chain(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
                    *, sm_scale, block_q, block_k, s_real, causal, window,
                    masked, mask_q_pad):
    """The shared backward recompute chain for one (q-tile, k-tile) pair:
    scores -> p (base-2 recompute against the saved row lse) -> dp -> ds.
    Each backward kernel accumulates its OWN gradients from the returned
    operands; the chain itself exists once (code-review r5 — the base-2
    and mask-elision changes previously had to be replicated into four
    kernel bodies).  ``masked`` is the boundary-tile instantiation;
    ``mask_q_pad`` says whether the mask must also cover pad q rows (the
    dK/dV-accumulating kernels — pad rows carry garbage lse; dq-only
    kernels discard pad rows' output downstream instead)."""
    k = k_ref[0]   # (Bk, D), input dtype
    v = v_ref[0]
    q = q_ref[0]   # (Bq, D)
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    bq, bk = q.shape[0], k.shape[0]
    # base-2 recompute: log2(e) folded into the score scale (see fwd)
    scores = _dot(q, k, ((1,), (1,))) * (sm_scale * _LOG2E)
    p = jnp.exp2(scores - lse * _LOG2E)  # recomputed probs, f32
    if masked:  # boundary tiles only — interior masks are all-true
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < s_real
        if mask_q_pad:
            mask = mask & (q_pos < s_real)
        if causal:
            mask = mask & (k_pos <= q_pos)
            if window:
                mask = mask & (k_pos > q_pos - window)
        p = jnp.where(mask, p, 0.0)
    dp = _dot(do, v, ((1,), (1,)))  # (Bq, Bk) f32
    ds = p * (dp - delta) * sm_scale
    return p, ds, q, k, do


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, sm_scale, block_q, block_k, n_q, s_real, causal,
                window):
    # grid (bh, k-tile, q-tile), q innermost; scratch accumulates dK/dV.
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _compute(masked):
        p, ds, q, _, do = _bwd_tile_chain(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            s_real=s_real, causal=causal, window=window, masked=masked,
            mask_q_pad=True)
        dv_sc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dk_sc[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    # causal skip: see the gating note in _fwd_kernel (same live condition;
    # here the q index maps are clamped instead of the K/V ones).  The
    # backward's padding mask covers BOTH sides (pad q rows carry garbage
    # lse), so interior needs the q-tile clear of the padding too.
    pad_ok = (
        True if s_real == n_q * block_q
        else ((ki + 1) * block_k <= s_real) & ((qi + 1) * block_q <= s_real)
    )
    _run_tiles(causal, qi, ki, block_q, block_k, _compute, window, pad_ok)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, dq_sc, *,
                      sm_scale, block_q, block_k, n_q, n_k, s_real, causal,
                      window):
    """The whole flash backward in ONE grid walk (VERDICT.md r3 item 2).

    The two-kernel scheme (dK/dV then dQ below, kept as the fallback)
    rebuilds ``scores``/``p``/``ds`` from scratch in each kernel — 7
    matmuls per live tile pair where 5 are semantically needed, plus a
    second full DMA sweep of q/k/v/do/lse/delta.  This kernel walks the
    dK/dV layout — grid (bh, k-tile, q-tile), q innermost — computes the
    recompute chain ONCE per live tile, and accumulates all three grads:
    dK/dV in per-k-tile scratch as before, dQ into a FULL-ROW (n_q,
    block_q, D) f32 VMEM scratch indexed by the q-tile id (each q-row
    block collects one contribution per k-tile; the row buffer is what
    makes cross-k accumulation possible without revisiting HBM blocks,
    and is why this kernel is gated on S*D fitting the VMEM budget — see
    ``_FUSED_DQ_VMEM_BUDGET``).  dQ flushes to its (1, S_pad, D) output
    block once per bh row, at the row's final grid step.
    """
    ji, qi = pl.program_id(1), pl.program_id(2)

    @pl.when((ji == 0) & (qi == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _compute(masked):
        p, ds, q, k, do = _bwd_tile_chain(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ji,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            s_real=s_real, causal=causal, window=window, masked=masked,
            mask_q_pad=True)
        dv_sc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dk_sc[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))
        dq_sc[qi] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    # causal skip: see the gating note in _fwd_kernel (dead steps skip the
    # compute AND the clamped q-side index maps elide their DMAs)
    pad_ok = (
        True if s_real == n_q * block_q
        else ((ji + 1) * block_k <= s_real) & ((qi + 1) * block_q <= s_real)
    )
    _run_tiles(causal, qi, ji, block_q, block_k, _compute, window, pad_ok)

    @pl.when(qi == n_q - 1)
    def _flush_dkv():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when((ji == n_k - 1) & (qi == n_q - 1))
    def _flush_dq():
        dq_ref[0] = dq_sc[...].reshape(dq_ref.shape[1:]).astype(dq_ref.dtype)


def _grouped_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, dq_sc, *,
                        sm_scale, block_q, block_k, n_qg, n_k, n_q, s_real,
                        causal, window):
    """The fused backward with a q-row-GROUP outer grid dim (round 5) —
    the long-row form of :func:`_fused_bwd_kernel`.

    The one-walk kernel is gated on dQ's whole row fitting VMEM
    (``_FUSED_DQ_VMEM_BUDGET``); past the gate, rows are split into
    ``G = n_q / n_qg`` groups and the grid becomes (bh, group, k-tile,
    q-tile-in-group) — each group walks ALL k-tiles against its own
    block of q rows, so its dQ scratch is bounded at (n_qg, block_q, D)
    and flushes once per group.  dK/dV still accumulate per k-tile
    inside a group, but now arrive in G per-group PARTIAL outputs
    (shape (bh, G, S_pad, D), block index (b_, g, j)) summed outside
    the kernel — an output block may only be revisited on consecutive
    grid steps, so cross-group accumulation cannot happen in scratch.
    Costs vs the one-walk form: K/V are swept once per group instead of
    once (the group-clamped index maps elide the sweeps a causal
    group's diagonal never reaches), plus the (G-1) extra partial-sum
    arrays; still ONE scores/p/ds recompute per live tile vs the
    two-kernel fallback's two.
    """
    g, ji, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    qi = g * n_qg + i  # global q-tile id (liveness/masks use this)

    @pl.when((ji == 0) & (i == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(i == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _compute(masked):
        p, ds, q, k, do = _bwd_tile_chain(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ji,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            s_real=s_real, causal=causal, window=window, masked=masked,
            mask_q_pad=True)
        dv_sc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dk_sc[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))
        dq_sc[i] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    pad_ok = (
        True if s_real == n_q * block_q
        else ((ji + 1) * block_k <= s_real) & ((qi + 1) * block_q <= s_real)
    )
    _run_tiles(causal, qi, ji, block_q, block_k, _compute, window, pad_ok)

    @pl.when(i == n_qg - 1)
    def _flush_dkv():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when((ji == n_k - 1) & (i == n_qg - 1))
    def _flush_dq():
        dq_ref[0] = dq_sc[...].reshape(dq_ref.shape[1:]).astype(dq_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
               *, sm_scale, block_q, block_k, n_k, s_real, causal, window):
    # grid (bh, q-tile, k-tile), k innermost; scratch accumulates dQ.
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _compute(masked):
        _, ds, _, k, _ = _bwd_tile_chain(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            s_real=s_real, causal=causal, window=window, masked=masked,
            mask_q_pad=False)
        dq_sc[...] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    # causal skip: see the gating note in _fwd_kernel.  dq's mask has no
    # q-side term (pad rows' dq is garbage sliced off by the caller), so
    # interior needs only the k-tile clear of the padding — but pad q rows
    # DO carry lse=0, whose exp(scores) stays finite and is discarded.
    pad_ok = True if s_real == n_k * block_k else (ki + 1) * block_k <= s_real
    _run_tiles(causal, qi, ki, block_q, block_k, _compute, window, pad_ok)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _to_bh(x, s_pad):
    b, s, h, d = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    if s_pad:
        x = jnp.pad(x, ((0, 0), (0, s_pad), (0, 0)))
    return x


def _prepare(q, k, v):
    """(B, S, H, D)/(B, S, H_kv, D) -> (B*H, S_pad, D)/(B*H_kv, S_pad, D)
    plus the static real sizes.

    Only the sequence is padded (to the 8-sublane tile); head_dim rides
    through unpadded — see the module docstring for why lane-padding D is
    pure waste.  ``H_kv < H`` is grouped-query attention: K/V stay at
    their own head count in HBM and the kernels' BlockSpec index maps
    route each q-head to its group's K/V block — no materialized
    ``jnp.repeat`` copies (that is the point of GQA's bandwidth story)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if h % max(1, hkv) or v.shape[2] != hkv:
        raise ValueError(
            f"q heads ({h}) must be a multiple of matching k/v heads "
            f"({k.shape[2]}/{v.shape[2]})"
        )
    s_pad = (-s) % 8
    return (_to_bh(q, s_pad), _to_bh(k, s_pad), _to_bh(v, s_pad),
            (b, s, h, d, hkv))


def _clamp_k_tile(kk, q_lo, q_hi, block_k: int, window: int):
    """DMA-elision clamp for a K/V tile index against the q rows
    [q_lo, q_hi] it serves: never past the causal diagonal's last live
    tile, and (with a sliding ``window``) never before the first
    in-window tile.  The SINGLE home of this formula — the per-q-tile
    BlockSpecs and the grouped path's per-group maps both call it
    (code-review r5: four inline copies had to stay mirrored by hand).
    MUST stay the dual of _run_tiles' liveness conditions."""
    kk = jnp.minimum(kk, q_hi // block_k)
    if window:
        kk = jnp.maximum(
            kk, jnp.maximum(0, (q_lo - window + 1) // block_k))
    return kk


def _clamp_q_tile(ii, k_lo, k_hi, block_q: int, window: int):
    """The q-side dual of :func:`_clamp_k_tile` for dK/dV-layout walks:
    clamp a q tile index against the k rows [k_lo, k_hi] — dead leading
    q-tiles clamp UP to the k-tile's first live q-tile, and with a
    window dead TRAILING q-tiles clamp DOWN to the last in-window one."""
    ii = jnp.maximum(ii, k_lo // block_q)
    if window:
        ii = jnp.minimum(ii, (k_hi + window - 1) // block_q)
    return ii


def _kv_spec(block_k: int, d: int, h: int, hkv: int, k_axis: int,
             causal_clamp_bq: int = 0, window: int = 0):
    """BlockSpec for a K/V operand under grouped heads: grid dim 0 runs
    over B*H q-heads; the index map folds that to the owning kv-head's row
    of the (B*H_kv, S_pad, D) array.  ``k_axis`` names which of the two
    non-leading grid indices walks the K/V sequence tiles.

    ``causal_clamp_bq`` (the q block size; fwd/dq layouts only) arms the
    causal tile-skip: dead above-diagonal steps get their k index CLAMPED
    to the last live tile (:func:`_clamp_k_tile`), so Pallas sees an
    unchanged block index and skips the DMA entirely while the kernel
    body skips the compute — the mechanism that makes the skip actually
    pay (see the gating note in _fwd_kernel)."""
    g = h // hkv

    def index_map(b_, i, j):
        kv_row = (b_ // h) * hkv + (b_ % h) // g
        kk = j if k_axis == 2 else i
        if causal_clamp_bq:
            qi = i if k_axis == 2 else j
            kk = _clamp_k_tile(kk, qi * causal_clamp_bq,
                               (qi + 1) * causal_clamp_bq - 1, block_k,
                               window)
        return (kv_row, kk, 0)

    return pl.BlockSpec((1, block_k, d), index_map)


def _q_side_spec(block_q: int, d_or_1: int, block_k: int,
                 causal_clamp: bool, window: int = 0):
    """BlockSpec for q/do/lse/delta in the dK/dV layout (grid (bh, k-tile,
    q-tile)): with the causal skip armed, dead leading q-tiles clamp UP to
    the k-tile's first live q-tile (and, with a sliding ``window``, dead
    TRAILING q-tiles clamp DOWN to the last in-window one) — same no-DMA
    trick as _kv_spec."""

    def index_map(b_, j, i):
        ii = i
        if causal_clamp:
            ii = _clamp_q_tile(ii, j * block_k, (j + 1) * block_k - 1,
                               block_q, window)
        return (b_, ii, 0)

    return pl.BlockSpec((1, block_q, d_or_1), index_map)


def _grid_params(interpret):
    if interpret:
        return {"interpret": True}
    return {
        "interpret": False,
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    }


def _fused_grid_params(interpret):
    # the fused backward accumulates dQ across BOTH non-leading grid dims
    # (every (k-tile, q-tile) step adds into the full-row scratch), so
    # only bh may be parallelized across cores
    if interpret:
        return {"interpret": True}
    return {
        "interpret": False,
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    }


def _grouped_grid_params(interpret):
    # 4-D grid (bh, group, k-tile, q-tile-in-group); the dq/dk/dv scratch
    # accumulations span the non-leading dims, so only bh parallelizes
    if interpret:
        return {"interpret": True}
    return {
        "interpret": False,
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary", "arbitrary", "arbitrary"),
        ),
    }


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, interpret, window):
    out, _ = _flash_fwd(q, k, v, causal, interpret, window)
    return out


def _flash_fwd(q, k, v, causal, interpret, window=0):
    interpret = resolve_interpret(interpret)
    qp, kp, vp, (b, s, h, d, hkv) = _prepare(q, k, v)
    bh, sp, _ = qp.shape
    block_q = _pick_block(sp, _FWD_BLOCK_Q or _BLOCK_Q)
    block_k = _pick_block(sp, _FWD_BLOCK_K or _BLOCK_K)
    n_k = sp // block_k
    sm_scale = d**-0.5
    kernel = partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        n_k=n_k, s_real=s, causal=causal, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sp // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            _kv_spec(block_k, d, h, hkv, k_axis=2,
                     causal_clamp_bq=block_q if causal else 0, window=window),
            _kv_spec(block_k, d, h, hkv, k_axis=2,
                     causal_clamp_bq=block_q if causal else 0, window=window),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # m
            pltpu.VMEM((block_q, 1), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),  # acc
        ],
        **_grid_params(interpret),
    )(qp, kp, vp)
    out_bshd = out[:, :s, :].reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return out_bshd, (q, k, v, out_bshd, lse)


def _flash_bwd(causal, interpret, window, res, g):
    q, k, v, out, lse = res
    gp, op, _, _ = _prepare(g, out, out)
    # delta_i = rowsum(dO_i * O_i) — the flash-bwd correction term
    delta = jnp.sum(gp.astype(jnp.float32) * op.astype(jnp.float32), axis=-1, keepdims=True)
    return _bwd_calls(q, k, v, g, lse, delta, causal, interpret, window)


def _bwd_calls(q, k, v, g, lse, delta, causal, interpret, window=0):
    """The two backward pallas calls from padded-layout lse/delta.

    ``lse``/``delta`` are (B*H, S_pad, 1) f32 — the GLOBAL row statistics.
    Factored out of :func:`_flash_bwd` so ring attention can drive the same
    kernels per K/V block with the statistics of the full ring
    (parallel/ring_attention.py)."""
    interpret = resolve_interpret(interpret)
    qp, kp, vp, (b, s, h, d, hkv) = _prepare(q, k, v)
    gp = _prepare(g, g, g)[0]
    bh, sp, _ = qp.shape
    block_q = _pick_block(sp, _BLOCK_Q)
    block_k = _pick_block(sp, _BLOCK_K)
    n_q = sp // block_q
    n_k = sp // block_k
    sm_scale = d**-0.5

    def from_bh(x, n_heads):
        return x[:, :s, :].reshape(b, n_heads, s, d).transpose(0, 2, 1, 3)

    def from_bh_grouped(x):
        x = x[:, :s, :].reshape(b, h, s, d)
        if hkv != h:
            x = x.reshape(b, hkv, h // hkv, s, d).sum(axis=2)
        return x.transpose(0, 2, 1, 3)

    # FUSED path (VERDICT.md r3 item 2): one grid walk produces dQ, dK and
    # dV — one scores/p/ds recompute instead of two (5 matmuls per live
    # tile, not 7) and one DMA sweep of the operands instead of two.  dQ
    # accumulates in a full-row f32 VMEM scratch, so the path is gated on
    # that buffer (plus dQ's whole-row output block) fitting alongside the
    # tile operands; longer rows fall back to the two-kernel scheme below.
    fused_row_bytes = sp * d * (4 + jnp.dtype(q.dtype).itemsize)
    if fused_row_bytes <= _FUSED_DQ_VMEM_BUDGET:
        dq_p, dk_p, dv_p = pl.pallas_call(
            partial(_fused_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
                    block_k=block_k, n_q=n_q, n_k=n_k, s_real=s,
                    causal=causal, window=window),
            grid=(bh, n_k, n_q),
            in_specs=[
                _q_side_spec(block_q, d, block_k, causal, window),   # q
                _kv_spec(block_k, d, h, hkv, k_axis=1),              # k
                _kv_spec(block_k, d, h, hkv, k_axis=1),              # v
                _q_side_spec(block_q, d, block_k, causal, window),   # do
                _q_side_spec(block_q, 1, block_k, causal, window),   # lse
                _q_side_spec(block_q, 1, block_k, causal, window),   # delta
            ],
            out_specs=[
                pl.BlockSpec((1, sp, d), lambda b_, j, i: (b_, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sp, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),       # dk tile
                pltpu.VMEM((block_k, d), jnp.float32),       # dv tile
                pltpu.VMEM((n_q, block_q, d), jnp.float32),  # dq full row
            ],
            **_fused_grid_params(interpret),
        )(qp, kp, vp, gp, lse, delta)
        return from_bh(dq_p, h), from_bh_grouped(dk_p), from_bh_grouped(dv_p)

    # GROUPED fused path (round 5): rows past the VMEM gate split into
    # budget-sized q-row groups — see _grouped_bwd_kernel.  One recompute
    # per live tile at the cost of G-1 extra K/V sweeps and per-group
    # partial dK/dV summed here.
    budget_rows = _GROUPED_DQ_VMEM_BUDGET // (d * (4 + jnp.dtype(q.dtype).itemsize))
    n_qg = min(n_q, max(1, budget_rows // block_q))
    while n_q % n_qg:
        n_qg -= 1
    if _GROUPED_BWD and 2 <= n_q // n_qg <= _GROUPED_MAX_GROUPS:
        n_groups = n_q // n_qg
        group_rows = n_qg * block_q
        g_fold = h // hkv

        def q_side_map(b_, g, j, i):
            ii = g * n_qg + i
            if causal:
                ii = _clamp_q_tile(ii, j * block_k, (j + 1) * block_k - 1,
                                   block_q, window)
            return (b_, ii, 0)

        def kv_map(b_, g, j, i):
            kv_row = (b_ // h) * hkv + (b_ % h) // g_fold
            jj = j
            if causal:
                # a causal group's diagonal never reaches k tiles past its
                # own last row: the same clamp at GROUP granularity elides
                # those whole sweeps
                jj = _clamp_k_tile(jj, g * n_qg * block_q,
                                   (g + 1) * n_qg * block_q - 1, block_k,
                                   window)
            return (kv_row, jj, 0)

        qspec = pl.BlockSpec((1, block_q, d), q_side_map)
        sspec = pl.BlockSpec((1, block_q, 1), q_side_map)
        kvspec = pl.BlockSpec((1, block_k, d), kv_map)
        dq_p, dk_g, dv_g = pl.pallas_call(
            partial(_grouped_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
                    block_k=block_k, n_qg=n_qg, n_k=n_k, n_q=n_q,
                    s_real=s, causal=causal, window=window),
            grid=(bh, n_groups, n_k, n_qg),
            in_specs=[qspec, kvspec, kvspec, qspec, sspec, sspec],
            out_specs=[
                pl.BlockSpec((1, group_rows, d),
                             lambda b_, g, j, i: (b_, g, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, g, j, i: (b_, g, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, g, j, i: (b_, g, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
                # partials stay f32 so dK/dV see ONE final rounding after
                # the cross-group sum, matching the fused and two-kernel
                # schemes' gradient precision (code-review r5); the cost
                # is a transient G-sized f32 array pair, freed at the sum
                jax.ShapeDtypeStruct((bh, n_groups, sp, d), jnp.float32),
                jax.ShapeDtypeStruct((bh, n_groups, sp, d), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),        # dk tile
                pltpu.VMEM((block_k, d), jnp.float32),        # dv tile
                pltpu.VMEM((n_qg, block_q, d), jnp.float32),  # dq group rows
            ],
            **_grouped_grid_params(interpret),
        )(qp, kp, vp, gp, lse, delta)
        dk_p = dk_g.sum(axis=1).astype(q.dtype)
        dv_p = dv_g.sum(axis=1).astype(v.dtype)
        return from_bh(dq_p, h), from_bh_grouped(dk_p), from_bh_grouped(dv_p)

    # dK/dV are produced PER Q-HEAD (shape B*H like q) and group-reduced
    # below: under GQA one kv-head serves h/hkv q-heads, and accumulating
    # across them inside the kernel would race the "parallel" grid dim.
    dkv = pl.pallas_call(
        partial(_dkv_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                n_q=n_q, s_real=s, causal=causal, window=window),
        grid=(bh, n_k, n_q),
        in_specs=[
            _q_side_spec(block_q, d, block_k, causal, window),            # q tile
            _kv_spec(block_k, d, h, hkv, k_axis=1),                       # k tile
            _kv_spec(block_k, d, h, hkv, k_axis=1),                       # v tile
            _q_side_spec(block_q, d, block_k, causal, window),            # do tile
            _q_side_spec(block_q, 1, block_k, causal, window),            # lse
            _q_side_spec(block_q, 1, block_k, causal, window),            # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sp, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),  # dk
            pltpu.VMEM((block_k, d), jnp.float32),  # dv
        ],
        **_grid_params(interpret),
    )(qp, kp, vp, gp, lse, delta)
    dk_p, dv_p = dkv

    dq_p = pl.pallas_call(
        partial(_dq_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                n_k=n_k, s_real=s, causal=causal, window=window),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            _kv_spec(block_k, d, h, hkv, k_axis=2,
                     causal_clamp_bq=block_q if causal else 0, window=window),
            _kv_spec(block_k, d, h, hkv, k_axis=2,
                     causal_clamp_bq=block_q if causal else 0, window=window),
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],  # dq
        **_grid_params(interpret),
    )(qp, kp, vp, gp, lse, delta)

    return from_bh(dq_p, h), from_bh_grouped(dk_p), from_bh_grouped(dv_p)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _lse_to_bsh(lse_p, b, s, h):
    """(B*H, S_pad, 1) f32 -> (B, S, H)."""
    return lse_p[:, :s, 0].reshape(b, h, s).transpose(0, 2, 1)


def _lse_to_padded(lse, s_pad):
    """(B, S, H) -> (B*H, S_pad, 1) f32 (zero padding; kernels mask pads)."""
    b, s, h = lse.shape
    out = lse.transpose(0, 2, 1).reshape(b * h, s, 1).astype(jnp.float32)
    if s_pad > s:
        out = jnp.pad(out, ((0, 0), (0, s_pad - s), (0, 0)))
    return out


def flash_block_fwd(q, k, v, causal: bool = False, interpret: bool | None = None):
    """One flash forward returning ``(out, lse)``, lse shaped (B, S, H).

    The ring-attention building block (parallel/ring_attention.py): the
    normalized block output plus its row logsumexp is exactly what the
    cross-device online-softmax merge needs to combine K/V blocks that live
    on different chips.  NOT differentiable — the ring writes its own VJP
    from :func:`flash_block_bwd`.
    """
    out, (_, _, _, _, lse_p) = _flash_fwd(q, k, v, causal, interpret)  # window=0: the ring handles cross-shard masking itself
    b, s, h, _ = q.shape
    return out, _lse_to_bsh(lse_p, b, s, h)


def flash_block_bwd(q, k, v, g, lse, delta, causal: bool = False,
                    interpret: bool | None = None):
    """Per-block flash backward under GLOBAL row statistics.

    ``lse``/``delta`` are (B, S, H) f32 for the FULL (ring-merged) softmax;
    returns this block's ``(dq_contribution, dk, dv)``.  With the true
    global statistics, ``p = exp(scores - lse)`` reproduces each block's
    share of the softmax exactly, so summing dq over blocks (and letting
    dk/dv ride the ring home) is the standard flash/ring backward.
    """
    s_pad = q.shape[1] + ((-q.shape[1]) % 8)
    lse_p = _lse_to_padded(lse, s_pad)
    delta_p = _lse_to_padded(delta, s_pad)
    return _bwd_calls(q, k, v, g, lse_p, delta_p, causal, interpret)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False, interpret: bool | None = None, window: int = 0,
) -> jax.Array:
    """Blockwise (flash) attention on (B, S, H, D); drop-in ``attn_fn`` for
    models/transformer.py.  ``interpret=None`` compiles under Mosaic (an
    error off-TPU) unless the process opted into the interpreter —
    ops/interpret.py.

    ``window`` > 0 is causal sliding-window attention: each position
    attends to the last ``window`` positions (itself included).  Off-window
    tiles are skipped for real — compute gated AND DMA elided via clamped
    index maps — so cost scales with S*window, not S^2 (the causal
    tile-skip machinery generalized)."""
    if window:
        if not causal:
            raise ValueError("window > 0 is causal sliding-window attention; "
                             "pass causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    return _flash(q, k, v, causal, interpret, window)
