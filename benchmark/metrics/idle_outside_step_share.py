"""Chip 0's idle time while the engine was not stepping at all (no
request to serve, or its caller's own code between two ``engine.step`` spans),
as a percentage of the traced window.  With the three other shares it sums to
``device_idle_share`` less the lead-in and lead-out of the traced window."""

from benchmark import host_spans


def read(r):
    return host_spans.idle_share(r, "outside_step")
