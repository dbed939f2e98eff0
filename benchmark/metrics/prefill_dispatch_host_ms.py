"""Median host milliseconds the engine spends dispatching one bucketed
prefill program: the program's ``site:prefill[b<bucket>]`` spans."""

from benchmark import host_spans

SITE = "site:prefill[b"


def read(r):
    return host_spans.median_ms(host_spans.spans(r.trace, SITE, prefix=True))
