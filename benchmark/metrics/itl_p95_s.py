"""95th percentile of the seconds between a request's successive tokens, as
the closed loop's clients received them, over every gap that ended in the
window (``sala_serve_runner.LongDocLoop``).  Unjudged: the cell is judged on
``out_tok_per_s``; this is what a scheduler that trades decode gaps for
throughput would move."""


def read(r):
    return r.counters.get("itl_p95_s")
