"""Prefill's flash-attention forward kernel against its roofline: for every
kernel call inside the engine's prefill program, the least time the chip
could take (operations and bytes from the call's shape: batch x heads rows,
bucket x bucket causal, ``benchmark/roofline.py``) over its device time."""

from benchmark import roofline

PROGRAM = "_prefill_row"


def read(r):
    if r.trace is None:
        return None
    cfg = r.cell.config
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    least = seconds = 0.0
    for shape, s, _ in r.trace.kernel_events(PROGRAM):
        if len(shape) != 3:
            continue
        bh, seq, d = shape
        ops, nbytes = roofline.flash_cost(bh, seq, seq, d, heads // kv, True, 0, 2)
        least += roofline.least_seconds(ops, nbytes, r.device["kind"])
        seconds += s
    return 100.0 * least / seconds if seconds > 0 else None
