"""How late the open loop's generator ran: 90th percentile of the seconds
between when a request was due and when it was handed to the engine.  A
starved generator must not read as a fast server."""


def read(r):
    return r.counters.get("gen_late_p90_s")
