"""The held latent experts' grouped products against their roofline, over
decode windows and prefill chunks alike: the least time the chip could take
for the pairs the device counted (``expert_load``) and the experts each call
hit (``expert_hits``: 11.0 MB of matrices each,
``benchmark/roofline_nemotron.py``) over the device time of the grouped
products' kernel calls (two-dimensional results, ``expert_ffn_dev_ms.py``)
in both programs."""

from benchmark import roofline, roofline_nemotron
from benchmark.metrics.expert_ffn_dev_ms import grouped_seconds

PROGRAMS = ("_window_impl", "_extend_row")


def read(r):
    load, hits = r.counters.get("expert_load"), r.counters.get("expert_hits")
    if r.trace is None or not load or not hits:
        return None
    seconds = grouped_seconds(r.trace, PROGRAMS)
    ops, nbytes = roofline_nemotron.latent_expert_cost(
        sum(map(sum, load)), sum(map(sum, hits)), r.cell.config)
    least = roofline.least_seconds(ops, nbytes, r.device["kind"])
    return 100.0 * least / seconds if seconds > 0 else None
