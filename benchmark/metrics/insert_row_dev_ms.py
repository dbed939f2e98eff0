"""Median device milliseconds of the engine's landing program, which copies
a prefilled row's pages into the page pool (``serving/kv_pool.py``
``make_paged_insert``).  Reads nothing where no row landed through it under
the trace: a chunked prefill lands through the extend program."""

PROGRAM = "_insert_row"


def read(r):
    if r.trace is None:
        return None
    s = r.trace.median_module_s(PROGRAM)
    return None if s is None else 1e3 * s
