"""Mean rows decoding per decode window over the measured window: the
program's ``ServingStats`` (``window_steps`` over ``n_windows``)."""


def read(r):
    return r.counters.get("decode_batch_mean")
