"""Device milliseconds of Pallas kernels per decode window: the summed
``custom-call`` time inside the engine's window program over the number of
windows traced.  The kernel there is the paged decode attention
(``ops/paged_attention.py``), once a layer; a window whose attention is the
``pool[block_table]`` gather holds no kernel, and the metric reads nothing."""

PROGRAM = "_window_impl"


def read(r):
    if r.trace is None:
        return None
    windows = len(r.trace.module_durations(PROGRAM))
    seconds = sum(s for _, s, _ in r.trace.kernel_events(PROGRAM))
    return 1e3 * seconds / windows if windows and seconds > 0 else None
