"""Chip 0's idle time in the rest of an engine iteration (``engine.emit``,
``engine.reset``, the deadline sweep and the bookkeeping of ``engine.step``),
as a percentage of the traced window.  With the three other shares it sums to
``device_idle_share`` less the lead-in and lead-out of the traced window."""

from benchmark import host_spans


def read(r):
    return host_spans.idle_share(r, "emit")
