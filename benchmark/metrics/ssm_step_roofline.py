"""The decode window's one-token SSD update against its roofline: the least
time the chip could take for the decoding row-steps the program counted
(``ssm_step_rows``: each reads and writes its row's float32 state,
``benchmark/roofline_nemotron.py``) over the device time of every
``ssd_step`` call inside the window program."""

from benchmark import roofline, roofline_nemotron

PROGRAM = "_window_impl"


def read(r):
    rows = r.counters.get("ssm_step_rows")
    if r.trace is None or not rows:
        return None
    seconds = sum(s for _, s, name in r.trace.kernel_events(PROGRAM)
                  if name.startswith("ssd_step"))
    ops, nbytes = roofline_nemotron.ssm_step_cost(rows, r.cell.config)
    least = roofline.least_seconds(ops, nbytes, r.device["kind"])
    return 100.0 * least / seconds if seconds > 0 else None
