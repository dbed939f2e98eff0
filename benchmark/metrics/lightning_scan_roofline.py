"""The lightning layers' chunk-scan kernel against its roofline: for every
call inside the extend program (the custom call with a three-dimensional
result, heads x tokens x D), the least time the chip could take
(``benchmark/roofline_sala.py``) over its device time."""

from benchmark import roofline, roofline_sala

PROGRAM = "_extend_row"


def read(r):
    if r.trace is None:
        return None
    least = seconds = 0.0
    for shape, s, _ in r.trace.kernel_events(PROGRAM):
        if len(shape) != 3:
            continue
        ops, nbytes = roofline_sala.lightning_scan_cost(*shape)
        least += roofline.least_seconds(ops, nbytes, r.device["kind"])
        seconds += s
    return 100.0 * least / seconds if seconds > 0 else None
