"""FLOPs accounting + MFU (model FLOPs utilization) reporting.

The reference had no FLOPs accounting at all (SURVEY.md §5 metrics row:
wall-clock prints only); MFU is the rebuild's chip-efficiency metric of
record next to images/sec/chip (VERDICT.md round-1 item 5).

FLOPs come from XLA's own cost analysis of the COMPILED program — the
honest count: it includes rematerialized forward passes under ``remat``,
excludes ops the compiler folded away, and under SPMD shardings reports the
per-device program's FLOPs (verified: an 8-way-sharded matmul reports 1/8
the single-device count), which is exactly the numerator MFU needs.

Two caveats, both verified on this backend: (1) a while-loop body is
counted ONCE regardless of trip count — callers must scale by their scan
trips (Trainer._epoch_flops does); (2) custom calls — Pallas kernels —
report no FLOPs (the sentinel -2), so a flash-attention model's cost
analysis is missing exactly the attention matmuls.  For its own flash
configs the Trainer closes that hole with :func:`attention_flops` — the
standard analytic model-FLOPs count — so reported MFU is real, not a
lower bound (VERDICT.md r2 item 2).  Models driving OTHER custom calls
through ``attn_fn`` remain lower bounds.

MFU denominator: the chip's peak matmul throughput at the dtype the model
computes in (bf16 for the zoo's default).  Peaks are keyed on
``device_kind`` from public TPU specs.  A TPU whose kind is not in the
table is an error, not a default; ``$DTM_PEAK_TFLOPS`` overrides the table
(and is the only option on CPU, where "peak" is ill-defined and MFU is
reported as None) and must parse as a number.
"""

from __future__ import annotations

import os

import jax

# bf16 dense peak TFLOP/s per chip, public spec-sheet numbers.
_PEAK_TFLOPS_BF16: dict[str, float] = {
    "TPU v2": 22.5,
    "TPU v3": 61.5,  # a.k.a. 123 per dual-core board
    "TPU v4i": 137.5,  # single-die inference chip — NOT a v4 variant
    "TPU v4": 275.0,  # 2-die training chip; device_kind names the chip
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
    "TPU v7": 2307.0,
}


def device_peak_tflops(device=None) -> float | None:
    """Peak bf16 TFLOP/s for ``device`` (default: first visible device).

    Longest-prefix match on ``device_kind`` so variants like
    "TPU v5 lite podslice" resolve consistently with their base kind
    ("TPU v4 ..." suffixed variants land on the same 275 as the exact
    kind; "TPU v4i" is its own, longer, entry and wins its own prefix);
    ``$DTM_PEAK_TFLOPS`` wins outright and must be a number.  Off the TPU
    platform an unknown kind returns None — callers report MFU as None
    rather than against a made-up peak; ON it, an unknown kind raises: an
    MFU of None on the machine the number is for hides a missing table row.
    """
    env = os.environ.get("DTM_PEAK_TFLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            raise ValueError(
                f"DTM_PEAK_TFLOPS={env!r} is not a number") from None
    device = device or jax.devices()[0]
    kind = str(getattr(device, "device_kind", "")).strip()
    best = None
    for prefix, peak in _PEAK_TFLOPS_BF16.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), peak)
    if best is None and getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"no bf16 peak for TPU device_kind {kind!r}: add its published "
            f"peak to utils/flops._PEAK_TFLOPS_BF16 "
            f"(known: {sorted(_PEAK_TFLOPS_BF16)})")
    return best[1] if best else None


def attention_flops(
    batch: int, seq: int, heads: int, head_dim: int, *,
    causal: bool = False, with_backward: bool = True, depth: int = 1,
    window: int = 0, cp: int = 1,
) -> float:
    """Analytic matmul FLOPs of multi-head attention, standard model-FLOPs
    convention: forward is the QK^T and PV matmuls (4*B*S^2*H*D), backward
    counted at 2x forward, causal attention halved (S^2/2 — the
    scaling-literature convention, which halves the diagonal too); a
    causal sliding ``window`` caps each query at ``min(q+1, W)`` keys,
    counted in the SAME half-diagonal convention: ``S*W - W^2/2`` scored
    pairs, exactly ``S^2/2`` at W = S — so MFU is continuous between
    window=S and window=0 runs of the same shape (r3 advisor).

    This is the MFU-numerator convention of the scaling literature — the
    FLOPs the computation semantically NEEDS.  The flash kernels execute
    somewhat more (the bwd recompute adds ~2 extra score matmuls, and tile
    granularity rounds the causal/window boundaries up), so an MFU built
    on this count is conservative w.r.t. what the MXU actually ran,
    matching how the dense path's XLA cost analysis treats it (validated
    against each other in tests/test_flops.py).

    ``cp > 1`` (ring attention over a context-parallel mesh, ISSUE 20)
    reports the PER-CHIP average: the semantic FLOPs of the whole
    attention are unchanged, but each of the ``cp`` chips scores only its
    S/cp queries against the rotating K/V blocks, so the per-chip MFU
    numerator is the total divided by ``cp`` (causal rings are load-
    imbalanced step by step, but the n-step total is uniform — the
    average is the honest steady-state figure).  Comm bytes are NOT
    FLOPs; charge those separately via :func:`ring_hop_bytes`.
    """
    if cp < 1:
        raise ValueError(f"cp must be >= 1, got {cp}")
    if causal and window:
        w = min(window, seq)
        pairs = seq * w - w * w / 2.0  # sum of min(q+1, W), half-diagonal conv.
        f = 4.0 * batch * pairs * heads * head_dim * depth
    else:
        f = 4.0 * batch * seq * seq * heads * head_dim * depth
        if causal:
            f /= 2.0
    if with_backward:
        f *= 3.0
    return f / cp


def decode_step_flops(
    batch: int, kv_span: int, dim: int, heads: int, head_dim: int, *,
    heads_kv: int | None = None, depth: int = 1, vocab: int = 0,
    cp: int = 1,
) -> float:
    """Analytic matmul FLOPs of ONE incremental decode step (S=1 per row),
    GQA-aware — the MFU numerator for serving decode benches.

    Per layer: q projection ``2*B*dim*(H*D)``, kv projection
    ``2*B*dim*(2*Hkv*D)`` — the GROUPED width: a ``heads_kv < heads``
    model computes and caches only ``Hkv`` key/value heads, and charging
    the full ``H`` here is exactly the over-report that made earlier
    bench MFU flatter GQA configs — out projection ``2*B*(H*D)*dim``, and
    the 4x MLP pair ``16*B*dim^2``.  Cache attention (QK^T + PV over the
    ``kv_span`` attended positions) is charged at the grouped cache width
    ``4*B*kv_span*Hkv*D`` — deliberately the CONSERVATIVE convention:
    each of the H query heads mathematically scores every cached
    position (an execution count of ``4*B*kv_span*H*D``), but the
    grouped figure is what the bandwidth-bound step streams from HBM and
    keeps reported MFU a lower bound instead of crediting GQA with
    shared-K work it never re-reads.  ``heads_kv=None`` (or ``== heads``)
    is MHA and reproduces the ungrouped count exactly.  Forward only —
    decode has no backward.  ``vocab > 0`` adds the final logits matmul
    ``2*B*dim*vocab`` (once, not per layer).

    ``cp > 1`` (context-parallel serving, ISSUE 20) is the PER-CHIP
    count: the sequence-sharded KV pool leaves each chip row attending
    over only ``ceil(kv_span / cp)`` cached positions, so the attention
    term shrinks to the per-chip width while the projections and MLP —
    replicated over the ``cp`` axis — stay whole.  The exact cp=1 delta
    is ``depth * 4*B*Hkv*D * (ceil(kv_span/cp) - kv_span)`` (pinned in
    tests/test_flops.py); the m/l/o merge psum it buys is comm, not
    FLOPs — see :func:`ring_hop_bytes`.
    """
    hkv = heads if heads_kv is None else heads_kv
    if not 0 < hkv <= heads:
        raise ValueError(f"heads_kv must be in 1..heads, got {hkv}/{heads}")
    if cp < 1:
        raise ValueError(f"cp must be >= 1, got {cp}")
    span_chip = -(-kv_span // cp)  # ceil: each chip row's attended width
    per_layer = (
        2.0 * batch * dim * heads * head_dim          # q projection
        + 2.0 * batch * dim * 2 * hkv * head_dim      # k+v projection
        + 4.0 * batch * span_chip * hkv * head_dim    # QK^T + PV (grouped)
        + 2.0 * batch * heads * head_dim * dim        # out projection
        + 16.0 * batch * dim * dim                    # MLP (4x, two mats)
    )
    return per_layer * depth + 2.0 * batch * dim * vocab


def ring_hop_bytes(
    seq_local: int, heads_kv: int, head_dim: int, *,
    batch: int = 1, dtype_bytes: int = 4, depth: int = 1,
) -> int:
    """Bytes ONE chip sends per ring hop of context-parallel prefill:
    the rotating K + V blocks at their GROUPED ``H_kv`` width (the
    grouped ring path never expands GQA before the hop — satellite 1 of
    ISSUE 20), ``2 * B * S_local * H_kv * D * dtype_bytes`` per layer.
    A full prefill performs ``cp - 1`` hops per layer, so total ring
    traffic per chip is ``(cp - 1) * ring_hop_bytes(...)``.  On
    this CPU-emulation box the ppermute is a memcpy; the byte count is
    the honest analytic charge for real-ICI projections."""
    if seq_local < 0 or heads_kv < 1 or head_dim < 1:
        raise ValueError(
            f"bad ring hop shape: seq_local={seq_local}, "
            f"heads_kv={heads_kv}, head_dim={head_dim}")
    return int(2 * batch * seq_local * heads_kv * head_dim
               * dtype_bytes * depth)


def compiled_flops(jitted_fn, *args) -> float | None:
    """Per-device FLOPs of one call of a jitted function, from XLA's cost
    analysis of the compiled (post-SPMD-partitioning) module.

    ``lower()`` re-traces but ``compile()`` hits the executable cache, so
    calling this on an already-hot function costs tracing time only.  None
    when the backend doesn't expose cost analysis.
    """
    try:
        cost = jitted_fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def mfu(flops_per_sec_per_chip: float | None, device=None) -> float | None:
    """flops/sec/chip -> fraction of the chip's bf16 peak (None off-table)."""
    if not flops_per_sec_per_chip:
        return None
    peak = device_peak_tflops(device)
    if not peak:
        return None
    return flops_per_sec_per_chip / (peak * 1e12)
