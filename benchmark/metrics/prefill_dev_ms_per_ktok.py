"""Device milliseconds of prefill per thousand real prompt tokens: the
device durations of the engine's prefill program in the trace over the
prompt tokens (padding not counted) admitted while it was traced."""

PROGRAM = "_prefill_row"


def read(r):
    tokens = r.counters.get("traced_prompt_tokens")
    if r.trace is None or not tokens:
        return None
    seconds = sum(r.trace.module_durations(PROGRAM))
    return 1e3 * seconds / (tokens / 1e3) if seconds > 0 else None
