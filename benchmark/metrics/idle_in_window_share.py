"""Chip 0's idle time while the engine launched a decode window, worked behind
it or waited for it (``engine.dispatch``, ``engine.overlap``, ``engine.readback``),
as a percentage of the traced window.  With the three other shares it sums to
``device_idle_share`` less the lead-in and lead-out of the traced window."""

from benchmark import host_spans


def read(r):
    return host_spans.idle_share(r, "window")
