"""Programs obtained during set-up (compiled or fetched from the persistent
cache): the program's ``CompileTracker``, snapshot delta around set-up."""


def read(r):
    return r.counters.get("setup_programs")
