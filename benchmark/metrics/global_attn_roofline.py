"""The global layers' paged decode kernel against its roofline: the least
time the chip could take for the pages the program's counter says the
window's steps read (``global_pages_read``, 2560 B a token at the model's
192-wide keys, ``benchmark/roofline_mimo.py``) over the kernel's device
time inside the window program: the custom calls named
``paged_decode_attention`` (the grouped products of the expert layers are
custom calls of the same program too, and are not this kernel)."""

from benchmark import roofline, roofline_mimo

PROGRAM = "_window_impl"


def read(r):
    pages = r.counters.get("global_pages_read")
    if r.trace is None or not pages:
        return None
    seconds = sum(s for _, s, name in r.trace.kernel_events(PROGRAM)
                  if name.startswith("paged_decode_attention"))
    ops, nbytes = roofline_mimo.global_decode_cost(
        pages, r.counters.get("decode_row_steps", 0), r.cell.config)
    least = roofline.least_seconds(ops, nbytes, r.device["kind"])
    return 100.0 * least / seconds if seconds > 0 else None
