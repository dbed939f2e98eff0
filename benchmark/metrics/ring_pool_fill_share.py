"""Share of the window layers' ring rows (one a slot) that hold a request's
last 128 keys and values at the end of the window: the program's
``ServingStats.ring_sample``."""


def read(r):
    total = r.counters.get("ring_rows_total")
    used = r.counters.get("ring_rows_in_use")
    return 100.0 * used / total if total and used is not None else None
