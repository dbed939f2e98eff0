"""Training's flash-attention kernels against their roofline: the least
time the chip could take for one forward and one backward call per layer
and step (operations and bytes from the shapes, ``benchmark/roofline.py``)
over the device time of every kernel call in the traced epochs.  A forward
pass recomputed for the backward adds time and no needed work."""

from benchmark import roofline


def read(r):
    c, cfg = r.counters, r.cell.config
    if r.trace is None or "train_program" not in c:
        return None
    calls = r.trace.kernel_events(c["train_program"])
    epochs = len(r.trace.module_durations(c["train_program"]))
    seconds = sum(s for _, s, _ in calls)
    if not calls or not epochs or seconds <= 0:
        return None
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    least = 0.0
    for matmuls in (2, 4):
        ops, nbytes = roofline.flash_cost(
            c["sequences_per_chip"] * heads, c["seq_len"], c["seq_len"],
            cfg["hidden_size"] // heads, heads // kv, True, c["window"], matmuls)
        least += roofline.least_seconds(ops, nbytes, r.device["kind"])
    least *= epochs * c["steps_per_epoch"] * cfg["num_hidden_layers"]
    return 100.0 * least / seconds
