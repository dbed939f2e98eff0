"""Collective time that nothing hides: seconds on chip 0 in which a
collective ran and no other operation did, over the seconds the chip was
busy.  Nothing to read on one chip."""


def read(r):
    if r.trace is None or r.device["count"] < 2:
        return None
    exposed, total = r.trace.exposed_collective_s()
    if total <= 0 or r.trace.busy_s <= 0:
        return None
    return 100.0 * exposed / r.trace.busy_s
