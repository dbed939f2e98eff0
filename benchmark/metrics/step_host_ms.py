"""Median host milliseconds of one engine iteration: the program's
``engine.step`` spans in which a decode window was dispatched."""

from benchmark import host_spans


def read(r):
    return host_spans.median_ms(host_spans.stepping(r.trace))
