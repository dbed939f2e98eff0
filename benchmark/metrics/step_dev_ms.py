"""Device milliseconds of one training step: the median device duration of
the trainer's epoch program in the trace, over its steps."""


def read(r):
    prog, steps = r.counters.get("train_program"), r.counters.get("steps_per_epoch")
    if r.trace is None or not prog or not steps:
        return None
    s = r.trace.median_module_s(prog)
    return None if s is None else 1e3 * s / steps
