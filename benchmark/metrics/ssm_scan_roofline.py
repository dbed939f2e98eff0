"""The Mamba layers' chunk-scan kernel against its roofline: for every
``ssd_chunk_scan`` call inside the extend program, the least time the chip
could take (``benchmark/roofline_nemotron.py``, from the call's result shape
heads x tokens x head_dim) over its device time.  A program without the
kernel reads nothing."""

from benchmark import roofline, roofline_nemotron

PROGRAM = "_extend_row"


def read(r):
    if r.trace is None:
        return None
    least = seconds = 0.0
    for shape, s, name in r.trace.kernel_events(PROGRAM):
        if not name.startswith("ssd_chunk_scan") or len(shape) != 3:
            continue
        ops, nbytes = roofline_nemotron.ssm_scan_cost(*shape, r.cell.config)
        least += roofline.least_seconds(ops, nbytes, r.device["kind"])
        seconds += s
    return 100.0 * least / seconds if seconds > 0 else None
