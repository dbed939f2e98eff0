"""Median host milliseconds of a request's first-token pick, the blocking
read of its token included: the program's ``engine.first_pick`` spans.
It is the host's wait for the prefill's logits."""

from benchmark import host_spans


def read(r):
    return host_spans.median_ms(host_spans.spans(r.trace, "engine.first_pick"))
