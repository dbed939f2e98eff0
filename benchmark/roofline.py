"""The table of peaks and the arithmetic of a kernel's roofline share.

Peaks are the chip's published ones, keyed by ``device_kind`` as JAX
reports it.  A device that is not in the table is an error, never a default.

A kernel's roofline share is the least time the chip could take for the
call (the larger of operations over peak FLOP/s and bytes over peak
bytes/s) over the time the trace shows.  Operations and bytes are what the
algorithm needs, from the shapes: the flash kernels' recomputation of the
scores in the backward pass is not counted, so a share errs low, not high.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            "benchmark/roofline.py: add it with its source, do not guess")
    return PEAKS[device_kind]


def attended_pairs(s_q: int, s_k: int, causal: bool, window: int) -> int:
    """Query-key pairs a row of heads attends to.  Causal with the queries
    the last ``s_q`` of ``s_k`` positions; ``window`` keeps the last
    ``window`` keys of each query (0: all)."""
    if not causal:
        return s_q * s_k
    first = s_k - s_q  # position of query 0
    if not window:
        return s_q * first + s_q * (s_q + 1) // 2
    full = max(0, min(s_q, s_k - max(window - 1, first)))  # queries with a full window
    bound = s_q - full  # queries that see fewer than `window` keys
    return full * window + bound * first + bound * (bound + 1) // 2


def flash_cost(bh: int, s_q: int, s_k: int, d: int, heads_per_kv: int,
               causal: bool, window: int, matmuls: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one flash-attention call over ``bh``
    (batch x query heads) rows.  ``matmuls`` is 2 for the forward pass
    (QK^T and PV) and 4 for the backward (dV, dP, dQ, dK).  Bytes are Q, O
    (and dO, dQ backward) at ``bh`` rows, K and V (and dK, dV) at
    ``bh / heads_per_kv`` rows, all bf16, plus the f32 log-sum-exp."""
    pairs = attended_pairs(s_q, s_k, causal, window)
    ops = 2.0 * matmuls * bh * pairs * d
    q_rows = bh * s_q * d * 2
    kv_rows = (bh // heads_per_kv) * s_k * d * 2
    lse = bh * s_q * 4
    if matmuls == 2:
        nbytes = 2 * q_rows + 2 * kv_rows + lse
    else:
        nbytes = 4 * q_rows + 4 * kv_rows + 2 * lse
    return ops, float(nbytes)


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the roofline's two bounds."""
    p = peaks(device_kind)
    return max(ops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
