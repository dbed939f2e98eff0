"""90th percentile of the seconds from sending a request to its first token,
over the closed loop's requests whose first token arrived in the window or
that were still waiting for it at the window's end (those count with their
wait so far).  A handful of requests: a coarse number, unjudged."""


def read(r):
    return r.counters.get("ttft_p90_s")
