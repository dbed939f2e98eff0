"""The readers this configuration brought, on a trace recorded on the chip
(``data_sala/trace_sample.json``: cut from a traced run of
``sala-d12.longdoc-closed`` on a TPU v5 lite, seed 4000000007, PR 28, by
``--dump-events`` with a sampler that kept twelve calls of each Pallas
kernel; the sampler is not kept): every program span, twelve to fifteen
calls of each of the three kernels, a few operations of every other kind.  The counters are the ones that go with what the sample holds
(five windows of 30 rows, four chunks), not the run's.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, roofline_sala, trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def reading():
    with open(os.path.join(HERE, "data_sala", "trace_sample.json")) as f:
        events = trace_reduce.Events.from_json(json.load(f))
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, "sala-d12.longdoc-closed", seed=1,
                             seconds=45.0, trace=True)
    counters = {
        # 15 decode calls = 5 windows x 3 sparse layers, 30 rows decoding,
        # each (row, layer, KV head) handed its 97 selected pages
        "sparse_blocks_read": 5 * 30 * 3 * 2 * 97, "sparse_blocks_live": 5 * 30 * 3 * 2 * 560,
        "decode_row_steps": 5 * 30, "dense_len_rows": 0,
        # 12 sparse-prefill calls = 4 chunks x 3 layers
        "prefill_chunk_starts": {16384: 2, 28672: 2},
        "state_rows_in_use": 32, "state_rows_total": 32,
    }
    device = {"kind": "TPU v5 lite", "memory_peak_bytes": 15906258944}
    return harness.Reading("", cell, counters, trace_reduce.Reduced(events, 1, 10.6), device)


def value(reading, name):
    reading.metric = name
    return harness.find_reader(name)(reading)


def test_the_three_kernels_are_told_apart_by_program_and_shape(reading):
    window = reading.trace.kernel_events("_window_impl")
    extend = reading.trace.kernel_events("_extend_row")
    assert {tuple(s) for s, _, _ in window} == {(64, 2, 16, 128)}
    assert {tuple(s) for s, _, _ in extend} == {(64, 2, 1024, 128), (32, 4096, 128)}
    assert all(n.startswith("paged_decode_attention") for _, _, n in window)


@pytest.mark.parametrize("name,low,high", [
    ("sparse_decode_roofline.sala", 15, 60),    # reads both KV heads: half at best
    ("sparse_prefill_roofline.sala", 3, 40),    # walks the whole live span
    ("lightning_scan_roofline.sala", 10, 40),   # float32 products on a bf16 peak
])
def test_roofline_shares_read_and_stay_under_100(reading, name, low, high):
    v = value(reading, name)
    assert low < v < high and v <= 100


def test_counter_and_program_readers(reading):
    assert value(reading, "sparse_read_share.sala") == pytest.approx(100 * 97 / 560)
    assert value(reading, "state_pool_fill_share.sala") == 100
    assert 15 < value(reading, "decode_window_dev_ms.sala") < 40
    assert 50 < value(reading, "prefill_chunk_dev_ms_per_ktok.sala") < 150
    assert value(reading, "hbm_peak_gb.sala") == pytest.approx(15.906258944)


def test_readers_read_nothing_where_the_program_has_nothing(reading):
    """On a program without these kernels or counters (the parent) a reader
    returns None and does not raise."""
    bare = harness.Reading("", reading.cell, {}, reading.trace, reading.device)
    for name in ("sparse_decode_roofline.sala", "sparse_prefill_roofline.sala",
                 "sparse_read_share.sala", "state_pool_fill_share.sala"):
        assert value(bare, name) is None


def test_necessary_work_counts_selected_keys_only():
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "minicpm-sala-serve-d12.json"))
    sp = roofline_sala.sparse_spec(cfg)
    assert roofline_sala.selected_blocks(sp) == 97
    assert roofline_sala.keys_of_query(8191, sp) == 8192          # dense
    assert roofline_sala.keys_of_query(8192, sp) == 96 * 64 + 1   # sparse
    ops, nbytes = roofline_sala.sparse_decode_cost(97, 1, cfg)
    assert nbytes == 2 * 97 * 64 * 128 * 2 + 2 * 3 * 32 * 128 * 2
    assert ops == 4 * 16 * 97 * 64 * 128
