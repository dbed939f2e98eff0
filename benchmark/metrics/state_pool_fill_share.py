"""Share of the recurrent-state pool's rows (one a slot) that hold a
request's state at the end of the window: the program's
``ServingStats.state_sample``."""


def read(r):
    total = r.counters.get("state_rows_total")
    used = r.counters.get("state_rows_in_use")
    return 100.0 * used / total if total and used is not None else None
