"""The program's own host spans in a reduced trace, and the device's idle
time under each of them.

``InferenceEngine.step()`` records its phases on the profiler's clock
(``utils/tracing.host_span``: ``engine.step`` around ``engine.admit``,
``engine.dispatch``, ``engine.overlap``, ``engine.readback``,
``engine.emit``, ``engine.reset``; every dispatch site ``site:<label>``).
They arrive in ``Events.host`` beside the runtime's spans.  The readers of
the "serving host loop" metrics take them from here by name: durations, and
chip 0's idle gaps cut by which phase the host was in.

A program without the spans (an earlier commit, the CPU rehearsal's
fixtures) gives empty lists and ``None``: the metric is then left out.
All times are nanoseconds on the profiler's clock, as in ``trace_reduce``.
"""

from __future__ import annotations

import functools
import statistics

from benchmark import trace_reduce as tr

STEP = "engine.step"
ADMIT = "engine.admit"
WINDOW = ("engine.dispatch", "engine.overlap", "engine.readback")


def spans(trace, name: str, prefix: bool = False) -> list[tuple[int, int]]:
    """``(start, end)`` of every host span called ``name`` (or whose name
    starts with it), on whichever thread, by start."""
    if trace is None:
        return []
    return sorted((s, s + d) for _thread, n, s, d in trace.events.host
                  if (n.startswith(name) if prefix else n == name))


def median_ms(found) -> float | None:
    return 1e-6 * statistics.median(e - s for s, e in found) if found else None


def stepping(trace) -> list[tuple[int, int]]:
    """The ``engine.step`` spans in which a decode window was dispatched."""
    dispatches = spans(trace, WINDOW[0])
    out, j = [], 0
    for s, e in spans(trace, STEP):
        while j < len(dispatches) and dispatches[j][0] < s:
            j += 1
        if j < len(dispatches) and dispatches[j][0] < e:
            out.append((s, e))
    return out


def idle_gaps(trace) -> list[tuple[int, int]]:
    """Chip 0's idle intervals between its first and its last operation:
    what ``Reduced.idle_gaps`` ranks, all of them."""
    if trace is None or not trace.devices:
        return []
    dev = trace.devices[0]
    busy = tr.union([(op[3], op[3] + op[4]) for op in dev["ops"] if not op[5]]
                    or [(m[1], m[1] + m[2]) for m in dev["modules"]])
    if not busy:
        return []
    return tr.subtract([(busy[0][0], busy[-1][1])], busy)


def intersect(a, b) -> list[tuple[int, int]]:
    """The parts of merged ``a`` that merged ``b`` covers."""
    return tr.subtract(a, tr.subtract(a, b))


@functools.lru_cache(maxsize=1)  # four readers ask for the one trace of a run
def idle_partition(trace) -> dict | None:
    """Chip 0's idle seconds by what the host was doing: inside
    ``engine.admit``; inside the window's launch, overlap and readback;
    in the rest of an ``engine.step`` (emit, reset, the sweep, the step's
    bookkeeping); under no ``engine.step`` at all.  The four sum to the
    gaps' total.  None without ``engine.step`` spans or device operations."""
    steps = tr.union(spans(trace, STEP))
    gaps = idle_gaps(trace)
    if not steps or not gaps:
        return None
    admit = tr.union(spans(trace, ADMIT))
    window = tr.union([iv for name in WINDOW for iv in spans(trace, name)])
    in_step = intersect(gaps, steps)
    past_admit = tr.subtract(in_step, admit)
    parts = {"admit": intersect(in_step, admit),
             "window": intersect(past_admit, window),
             "emit": tr.subtract(past_admit, window),
             "outside_step": tr.subtract(gaps, steps)}
    return {k: tr.length(v) / 1e9 for k, v in parts.items()}


def idle_share(reading, part: str) -> float | None:
    """``part``'s idle seconds as a percentage of the traced window: the
    denominator of ``device_idle_share``."""
    if reading.trace is None or reading.trace.window_s <= 0:
        return None
    parts = idle_partition(reading.trace)
    return None if parts is None else 100.0 * parts[part] / reading.trace.window_s
