"""Median device milliseconds of the engine's decode-window program."""

PROGRAM = "_window_impl"


def read(r):
    if r.trace is None:
        return None
    s = r.trace.median_module_s(PROGRAM)
    return None if s is None else 1e3 * s
