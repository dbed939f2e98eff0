"""Device milliseconds of chunked prefill per thousand chunk tokens: the
device durations of the engine's extend program in the trace over the tokens
of its calls (every call is one chunk of the configuration's
``prefill_chunk`` tokens; a prompt's last chunk is padded to it, and the
program computes the padding too)."""

PROGRAM = "_extend_row"


def read(r):
    if r.trace is None:
        return None
    runs = r.trace.module_durations(PROGRAM)
    chunk = r.cell.config["engine"].get("prefill_chunk")
    if not runs or not chunk:
        return None
    return 1e3 * sum(runs) / (len(runs) * chunk / 1e3)
