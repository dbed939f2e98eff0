"""Median seconds a request of the window waited in the scheduler's queue:
the program's own ``Request.admit_t - Request.submit_t``."""


def read(r):
    return r.counters.get("queue_wait_p50_s")
