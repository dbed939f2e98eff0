"""The prefill chunk's block-sparse attention kernel against its roofline:
the least time the chip could take for what each chunk's queries SELECT
(from the chunks' first positions, which the program counts, and the
configuration's selection sizes: ``benchmark/roofline_sala.py``) over the
kernel's device time inside the extend program.  The kernel is the
custom call with a four-dimensional result (tiles, KV heads, rows, D)."""

from benchmark import roofline, roofline_sala

PROGRAM = "_extend_row"


def read(r):
    starts = r.counters.get("prefill_chunk_starts")
    if r.trace is None or not starts:
        return None
    seconds = sum(s for shape, s, _ in r.trace.kernel_events(PROGRAM)
                  if len(shape) == 4)
    ops, nbytes = roofline_sala.sparse_prefill_cost(
        starts, r.cell.config["engine"]["prefill_chunk"], r.cell.config)
    least = roofline.least_seconds(ops, nbytes, r.device["kind"])
    return 100.0 * least / seconds if seconds > 0 else None
