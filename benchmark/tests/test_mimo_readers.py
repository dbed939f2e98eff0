"""The readers this configuration brought, on a trace recorded on the chip
(``data_mimo/trace_sample.json``: cut from a traced run of
``mimo-d7.mixed-closed`` on a TPU v5 lite, seed 3000000019, PR 32, by
``--dump-events`` with a sampler that kept eighty kernel calls; the sampler
is not kept): the first two iterations of the window, each ONE decode
window (two paged-attention calls, eighteen grouped products) and ONE
prefill chunk (eighteen grouped products), with a few operations of every
other kind.  The counters are the ones that go with what the sample holds
(two windows of 96 rows, two chunks), not the run's.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, roofline_mimo, trace_reduce  # noqa: E402

CELL = "mimo-d7.mixed-closed"


@pytest.fixture(scope="module")
def reading():
    with open(os.path.join(HERE, "data_mimo", "trace_sample.json")) as f:
        events = trace_reduce.Events.from_json(json.load(f))
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, CELL, seed=1, seconds=45.0, trace=True)
    counters = {
        # two windows of 96 rows at ~6.2k tokens: 97 pages a row in each of
        # the two global layers
        "global_pages_read": 2 * 96 * 2 * 97, "decode_row_steps": 2 * 96,
        # per expert layer and held expert over two windows and two chunks:
        # ~3 pairs a window and ~64 a chunk, nearly every expert hit
        "expert_load": [[2 * 3 + 2 * 64] * 16] * 6,
        "expert_hits": [[4] * 15 + [3]] * 6,
        "expert_assignments": (2 * 96 + 2 * 2048) * 8 * 6,
        "expert_assignments_held": 6 * 16 * (2 * 3 + 2 * 64),
        "ring_rows_in_use": 96, "ring_rows_total": 96,
    }
    device = {"kind": "TPU v5 lite", "memory_peak_bytes": 15529946112}
    return harness.Reading("", cell, counters, trace_reduce.Reduced(events, 1, 0.158), device)


def value(reading, name):
    reading.metric = name
    return harness.find_reader(name)(reading)


def test_the_kernels_are_told_apart_by_name_and_shape(reading):
    window = reading.trace.kernel_events("_window_impl")
    extend = reading.trace.kernel_events("_extend_row")
    assert len(window) == 2 * (2 + 18) and len(extend) == 2 * 18
    paged = [(tuple(s), n) for s, _, n in window if len(s) == 4]
    assert {s for s, _ in paged} == {(96, 4, 16, 128)} and len(paged) == 4
    assert all(n.startswith("paged_decode_attention") for _, n in paged)
    # the grouped products: pairs x columns, 768 = 96 rows x top-8
    assert {tuple(s) for s, _, _ in window if len(s) == 2} == {(768, 2048), (768, 4096)}
    assert {tuple(s) for s, _, _ in extend} == {(16384, 2048), (16384, 4096)}


@pytest.mark.parametrize("name,low,high", [
    ("expert_ffn_roofline.mimo", 40, 100),   # weight stream of the experts hit
    ("global_attn_roofline.mimo", 50, 100),  # counted at 192 of the 256 stored
])
def test_roofline_shares_read_and_stay_under_100(reading, name, low, high):
    v = value(reading, name)
    assert low < v <= high


def test_counter_and_program_readers(reading):
    assert 3 < value(reading, "expert_ffn_dev_ms.mimo") < 9
    assert 10 < value(reading, "decode_window_dev_ms.mimo") < 25
    assert 20 < value(reading, "prefill_chunk_dev_ms_per_ktok.mimo") < 60
    assert value(reading, "expert_held_share.mimo") == pytest.approx(
        100 * 16 * 134 / ((192 + 4096) * 8))
    assert value(reading, "expert_load_peak_ratio.mimo") == 1.0
    assert value(reading, "ring_pool_fill_share.mimo") == 100
    assert value(reading, "hbm_peak_gb.mimo") == pytest.approx(15.529946112)


def test_readers_read_nothing_where_the_program_has_nothing(reading):
    """On a program without these kernels or counters (the parent) a reader
    returns None and does not raise."""
    bare = harness.Reading("", reading.cell, {}, reading.trace, reading.device)
    for name in ("expert_ffn_roofline.mimo", "global_attn_roofline.mimo",
                 "expert_held_share.mimo", "expert_load_peak_ratio.mimo",
                 "ring_pool_fill_share.mimo"):
        assert value(bare, name) is None
    untraced = harness.Reading("", reading.cell, reading.counters, None, reading.device)
    for name in ("expert_ffn_dev_ms.mimo", "expert_ffn_roofline.mimo",
                 "global_attn_roofline.mimo"):
        assert value(untraced, name) is None


def test_necessary_work_counts_the_models_widths():
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "mimo-v2-flash-serve-d7.json"))
    ops, nbytes = roofline_mimo.expert_ffn_cost(10, 3, cfg)
    assert nbytes == 3 * 3 * 4096 * 2048 * 2 + 2 * 10 * 4096 * 2
    assert ops == 6 * 10 * 4096 * 2048
    # a token of a global layer's page: 4 KV heads x (192 + 128) x 2 B
    ops, nbytes = roofline_mimo.global_decode_cost(1, 0, cfg)
    assert nbytes == 64 * 2560 and ops == 2 * 64 * 64 * 320
