"""The readers this configuration brought, and the existing readers it
reuses under the ``.nemo`` suffix, on a trace recorded on the chip
(``data_nemotron/trace_sample.json``: cut from a traced run of
``nemotron-d11.agent-closed`` on a TPU v5 lite, seed 3500000029, by
``--dump-events`` with a sampler that kept the first two decode windows and
the two prefill chunks between them whole as to kernel calls — per window
five ``ssd_step`` calls, ten grouped products over 128 rows x top-22 pairs
and one ``paged_decode_attention`` call; per chunk five ``ssd_chunk_scan``
calls and ten grouped products over 2048 x 22 pairs — three operations of
every other kind and the host spans of 50 us or more; the sampler is not
kept).  Each expected value is computed here from the sample's own events,
by the metric's definition.  The counters are the ones that go with what
the sample holds (two windows of 128 rows, two chunks), not the run's.
"""

import json
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, roofline, roofline_nemotron, trace_reduce  # noqa: E402

CELL = "nemotron-d11.agent-closed"
KIND = "TPU v5 lite"
COUNTERS = {
    # two windows of 128 decoding rows through five Mamba layers
    "ssm_step_rows": 2 * 128 * 5, "ssm_scan_tokens": 2 * 2048 * 5,
    # per expert layer and held expert over two windows and two chunks:
    # ~5.5 pairs a window and 88 a chunk, every expert hit in each call
    "expert_load": [[2 * 5 + 2 * 88] * 128] * 5,
    "expert_hits": [[4] * 128] * 5,
    "expert_assignments": (2 * 128 + 2 * 2048) * 22 * 5,
    "expert_assignments_held": 5 * 128 * (2 * 5 + 2 * 88),
    "decode_batch_mean": 128.0, "kv_pool_fill_share": 80.7,
    "state_rows_in_use": 128, "state_rows_total": 128,
    "itl_p95_s": 0.1293, "ttft_p90_s": 25.50,
}


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(HERE, "data_nemotron", "trace_sample.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reading(sample):
    events = trace_reduce.Events.from_json(sample)
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, CELL, seed=1, seconds=45.0, trace=True)
    device = {"kind": KIND, "memory_peak_bytes": 14_674_373_120}
    return harness.Reading("", cell, dict(COUNTERS),
                           trace_reduce.Reduced(events, 1, 0.2118), device)


def value(reading, name):
    reading.metric = name
    return harness.find_reader(name)(reading)


def runs(sample, program):
    """(start, duration) in ns of each run of ``program`` in the sample."""
    return [(m[1], m[2]) for m in sample["devices"][0]["modules"]
            if m[0].startswith(f"jit_{program}(")]


def kernel_ns(sample, program, name=None, ndim=None):
    """Summed ns of the kernel calls inside ``program``'s runs, by name
    prefix or by the rank of their result shape."""
    spans = runs(sample, program)
    return sum(op[4] for op in sample["devices"][0]["ops"]
               if op[1] == "custom-call"
               and any(s <= op[3] < s + d for s, d in spans)
               and (name is None or op[0].startswith(name))
               and (ndim is None or len(op[2]) == ndim))


def test_the_sample_holds_what_its_docstring_says(sample, reading):
    assert len(runs(sample, "_window_impl")) == len(runs(sample, "_extend_row")) == 2
    window = reading.trace.kernel_events("_window_impl")
    extend = reading.trace.kernel_events("_extend_row")
    shapes = {}
    for shape, _, name in window + extend:
        key = (name.rstrip("0123456789."), tuple(shape))
        shapes[key] = shapes.get(key, 0) + 1
    assert shapes == {
        ("ssd_step", (128, 8, 64, 16)): 10,
        ("gmm", (2816, 2688)): 10, ("gmm", (2816, 1024)): 10,
        ("paged_decode_attention", (128, 2, 16, 128)): 2,
        ("ssd_chunk_scan", (128, 2048, 64)): 10,
        ("gmm", (45056, 2688)): 10, ("gmm", (45056, 1024)): 10,
    }


def test_ssm_step_roofline_reads_the_recorded_share(sample, reading):
    """1280 row-steps, each 2 x 4.19 MB of state + x, dt, B, C and y, over
    the ten recorded ``ssd_step`` calls."""
    ops, nbytes = roofline_nemotron.ssm_step_cost(1280, reading.cell.config)
    assert nbytes == 1280 * (2 * 128 * 64 * 128 * 4 + 2 * 128 * 64 * 2 + 128 * 4
                             + 2 * 8 * 128 * 2)
    seconds = kernel_ns(sample, "_window_impl", "ssd_step") / 1e9
    want = 100 * roofline.least_seconds(ops, nbytes, KIND) / seconds
    assert value(reading, "ssm_step_roofline.nemo") == pytest.approx(want)
    assert 50 < want < 100


def test_ssm_scan_roofline_reads_the_recorded_share(sample, reading):
    ops, nbytes = roofline_nemotron.ssm_scan_cost(128, 2048, 64, reading.cell.config)
    seconds = kernel_ns(sample, "_extend_row", "ssd_chunk_scan") / 1e9
    want = 100 * 10 * roofline.least_seconds(ops, nbytes, KIND) / seconds
    assert value(reading, "ssm_scan_roofline.nemo") == pytest.approx(want)
    assert 0 < want < 100


def test_latent_expert_roofline_reads_the_recorded_share(sample, reading):
    """The grouped products (two-dimensional results) of both programs
    against 5 x 128 x 186 pairs and 5 x 128 x 4 hits of 11.0 MB each."""
    ops, nbytes = roofline_nemotron.latent_expert_cost(5 * 128 * 186, 5 * 128 * 4,
                                                       reading.cell.config)
    assert nbytes == 2 * 5 * 128 * 4 * 1024 * 2688 * 2 + 2 * 5 * 128 * 186 * 1024 * 2
    seconds = (kernel_ns(sample, "_window_impl", ndim=2)
               + kernel_ns(sample, "_extend_row", ndim=2)) / 1e9
    want = 100 * roofline.least_seconds(ops, nbytes, KIND) / seconds
    assert value(reading, "latent_expert_roofline.nemo") == pytest.approx(want)
    assert 0 < want < 100


def test_paged_decode_reads_the_paged_kernel_alone(sample, reading):
    """``paged_decode_dev_ms`` takes the paged kernel by name; the accepted
    ``paged_attn_dev_ms`` sums every kernel of the window, which here is the
    state-space updates and the grouped products as well."""
    want = 1e3 * kernel_ns(sample, "_window_impl", "paged_decode_attention") / 1e9 / 2
    assert value(reading, "paged_decode_dev_ms.nemo") == pytest.approx(want)
    every = 1e3 * kernel_ns(sample, "_window_impl") / 1e9 / 2
    assert value(reading, "paged_attn_dev_ms.nemo") == pytest.approx(every)
    assert every > 10 * want


def test_the_suffix_forms_read_what_their_readers_read(sample, reading):
    """The existing readers, unedited, under ``.nemo``: the program spans,
    the host spans, the counters and the device record of this cell."""
    windows = [d for _, d in runs(sample, "_window_impl")]
    chunks = [d for _, d in runs(sample, "_extend_row")]
    assert value(reading, "decode_window_dev_ms.nemo") == pytest.approx(
        statistics.median(windows) / 1e6)
    assert value(reading, "prefill_chunk_dev_ms_per_ktok.nemo") == pytest.approx(
        sum(chunks) / 1e6 / (len(chunks) * 2.048))
    assert value(reading, "decode_batch_mean.nemo") == 128.0
    assert value(reading, "kv_pool_fill_share.nemo") == 80.7
    assert value(reading, "state_pool_fill_share.nemo") == 100.0
    assert value(reading, "expert_held_share.nemo") == pytest.approx(
        100 * 5 * 128 * 186 / ((2 * 128 + 2 * 2048) * 22 * 5))
    assert value(reading, "expert_load_peak_ratio.nemo") == 1.0
    assert value(reading, "hbm_peak_gb.nemo") == pytest.approx(14.674373120)
    assert value(reading, "itl_p95_s.nemo") == 0.1293
    assert value(reading, "ttft_p90_s.nemo") == 25.50
    idle = value(reading, "device_idle_share.nemo")
    assert idle == pytest.approx(100 * (1 - reading.trace.busy_s / 0.2118)) and 0 < idle < 100
    # the engine.step spans that dispatched a window
    host = sample["host"]
    steps = [(s, s + d) for _, n, s, d in host if n == "engine.step"]
    dispatch = [s for _, n, s, _ in host if n == "engine.dispatch"]
    stepping = [e - s for s, e in steps if any(s <= t < e for t in dispatch)]
    assert len(stepping) == 3
    assert value(reading, "step_host_ms.nemo") == pytest.approx(
        statistics.median(stepping) / 1e6)


def test_readers_read_nothing_where_the_program_has_nothing(reading):
    """On a program without these kernels or counters (the parent) a reader
    returns None and does not raise."""
    bare = harness.Reading("", reading.cell, {}, reading.trace, reading.device)
    for name in ("ssm_step_roofline.nemo", "latent_expert_roofline.nemo",
                 "expert_held_share.nemo", "state_pool_fill_share.nemo",
                 "itl_p95_s.nemo", "ttft_p90_s.nemo"):
        assert value(bare, name) is None
    untraced = harness.Reading("", reading.cell, reading.counters, None, reading.device)
    for name in ("ssm_scan_roofline.nemo", "ssm_step_roofline.nemo",
                 "latent_expert_roofline.nemo", "decode_window_dev_ms.nemo",
                 "paged_decode_dev_ms.nemo"):
        assert value(untraced, name) is None
