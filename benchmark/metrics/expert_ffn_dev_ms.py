"""Device milliseconds a decode window spends in the held experts' grouped
matrix products: inside the engine's window program, every Pallas kernel
call (a ``custom-call``) whose result is two-dimensional, pairs x columns
(the grouped product of ``parallel/expert_parallel.py``, three a layer; the
paged attention kernel beside it returns rows x KV heads x group x width),
over the number of windows traced.  A program without an expert layer holds
no such call, and the metric reads nothing."""

PROGRAMS = ("_window_impl",)


def grouped_seconds(trace, programs) -> float:
    return sum(s for prog in programs
               for shape, s, _ in trace.kernel_events(prog) if len(shape) == 2)


def read(r):
    if r.trace is None:
        return None
    windows = len(r.trace.module_durations(PROGRAMS[0]))
    seconds = grouped_seconds(r.trace, PROGRAMS)
    return 1e3 * seconds / windows if windows and seconds > 0 else None
