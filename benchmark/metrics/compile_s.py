"""Seconds the compiler (or the persistent cache's lookup) took during
set-up: the program's ``CompileTracker``, snapshot delta around set-up."""


def read(r):
    return r.counters.get("compile_s")
