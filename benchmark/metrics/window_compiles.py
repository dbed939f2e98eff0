"""Programs compiled inside the measured window: must read 0.  The
program's ``CompileTracker``, snapshot delta around the window."""


def read(r):
    return r.counters.get("window_compiles")
