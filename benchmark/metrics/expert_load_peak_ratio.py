"""The busiest held expert's pairs over the mean held expert's, over the
window and all expert layers (the device's ``expert_load``): 1 is even
routing; the straggler a deployment's exchange would wait for."""


def read(r):
    load = [n for layer in r.counters.get("expert_load") or [] for n in layer]
    mean = sum(load) / len(load) if load else 0
    return max(load) / mean if mean else None
