"""Device milliseconds of the paged decode attention kernel per decode
window: the calls named ``paged_decode_attention`` (``ops/paged_attention.py``)
inside the engine's window program, summed, over the number of windows
traced.  ``paged_attn_dev_ms`` sums EVERY kernel of the window, which is
the paged kernel alone only where the window runs no other kernel; a window
that also runs state-space updates or grouped expert products needs the
kernel told apart by name.  A window without the kernel reads nothing."""

PROGRAM = "_window_impl"
KERNEL = "paged_decode_attention"


def read(r):
    if r.trace is None:
        return None
    windows = len(r.trace.module_durations(PROGRAM))
    seconds = sum(s for _, s, name in r.trace.kernel_events(PROGRAM)
                  if name.startswith(KERNEL))
    return 1e3 * seconds / windows if windows and seconds > 0 else None
