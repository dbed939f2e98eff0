"""Decode's paged-attention kernel against its roofline when it is handed
SELECTED pages: the least time the chip could take for the blocks the
program's counters say were read (one KV head's bytes of a page each,
``benchmark/roofline_sala.py``) over the kernel's device time inside the
window program."""

from benchmark import roofline, roofline_sala

PROGRAM = "_window_impl"


def read(r):
    blocks = r.counters.get("sparse_blocks_read")
    if r.trace is None or not blocks:
        return None
    seconds = sum(s for _, s, _ in r.trace.kernel_events(PROGRAM))
    ops, nbytes = roofline_sala.sparse_decode_cost(
        blocks, r.counters.get("decode_row_steps", 0), r.cell.config)
    least = roofline.least_seconds(ops, nbytes, r.device["kind"])
    return 100.0 * least / seconds if seconds > 0 else None
