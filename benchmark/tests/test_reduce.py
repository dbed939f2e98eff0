"""The trace reduction and the roofline arithmetic, on event lists in the
reduction's own intermediate form: one made by hand, whose every number can
be checked on paper, and two cut from this PR's chip traces by
``trace_reduce.sample_events`` (``data/events_*.json``, a few kB each)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import roofline, trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def by_hand():
    """Two runs of one program, 10 ms each, 5 ms apart; inside each a
    while (container), a fusion of 6 ms, a kernel of 3 ms and an all-reduce
    of 4 ms whose last 1 ms nothing overlaps; an async copy over all of it."""
    ops, modules = [], []
    for base in (0, 15 * MS):
        modules.append(["jit_run_epoch(123)", base, 10 * MS])
        ops += [
            ["while.7", "while", [], base, 10 * MS, False],
            ["convolution_add_fusion.2", "fusion", [4, 8], base, 6 * MS, False],
            ["block_0.20", "custom-call", [96, 4096, 128], base + 6 * MS, 3 * MS, False],
            ["all-reduce.1", "all-reduce", [8], base + 5 * MS, 4 * MS, True],
            ["copy-start.3", "copy-start", [8], base, 10 * MS, True],
        ]
    host = [["python3", "_bench:readback", 9 * MS, 7 * MS],
            ["python3", "PjitFunction(run_epoch)", 12 * MS, 1 * MS],
            ["main/1", "Execute", 0, 30 * MS]]
    return tr.Events(devices=[{"id": 0, "modules": modules, "ops": ops}], host=host)


def test_busy_idle_and_gap_names():
    r = tr.Reduced(by_hand(), 1, 0.030)
    assert r.busy_s == pytest.approx(0.020)  # union of the synchronous ops
    assert r.module_durations("run_epoch") == [0.010, 0.010]
    assert r.median_module_s("run_epoch") == 0.010
    gaps = r.idle_gaps()
    # one gap of 5 ms; its middle (12.5 ms) lies in both Python spans and
    # the innermost (shortest) one names it
    assert gaps == [["python3:PjitFunction(run_epoch)", pytest.approx(0.005)]]


def test_device_ops_leave_containers_and_async_out():
    ops = dict(tr.Reduced(by_hand(), 1, 0.030).device_ops())
    assert ops == {"run_epoch/convolution_add_fusion": pytest.approx(0.012),
                   "run_epoch/tpu_custom_call": pytest.approx(0.006)}


def test_kernel_events_and_exposed_collective():
    r = tr.Reduced(by_hand(), 1, 0.030)
    calls = r.kernel_events("run_epoch")
    assert [(c[0], c[1]) for c in calls] == [([96, 4096, 128], 0.003)] * 2
    assert r.kernel_events("no_such_program") == []
    exposed, total = r.exposed_collective_s()
    # the all-reduce runs 5..9 ms; fusion covers to 6, the kernel 6..9: hidden
    assert total == pytest.approx(0.008) and exposed == pytest.approx(0.0)
    ev = by_hand()
    ev.devices[0]["ops"] = [o for o in ev.devices[0]["ops"] if o[1] != "custom-call"]
    exposed, _ = tr.Reduced(ev, 1, 0.030).exposed_collective_s()
    assert exposed == pytest.approx(0.006)  # 6..9 ms now bare, twice


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 3), (2, 4), (6, 6)]) == [(0, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]


def test_program_names():
    assert tr.program_of("jit__window_impl(4828526723788095013)") == "_window_impl"
    assert tr.program_of("jit_run_epoch(9893929508652972922)") == "run_epoch"


RECORDED = sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith("events_") and f.endswith(".json"))


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_chip_events(name):
    """Cut from a v5e trace of this PR: the raw operation names parse to
    the kinds they were filed under, programs are found by name, kernels
    carry a (rows, sequence, head) shape, and busy time is inside the
    span of the events."""
    d = json.load(open(os.path.join(HERE, "data", name)))
    ev = tr.Events.from_json(d)
    for kind, raw in ev.raw.items():
        n, opcode, _ = tr.parse_op(raw)
        assert tr.kind_of(n, opcode) == kind
    assert "tpu_custom_call" in ev.raw and "custom-call(" in ev.raw["tpu_custom_call"]
    r = tr.Reduced(ev, 1, d["window_s"])
    assert 0 < r.busy_s <= d["window_s"]
    assert r.module_durations(d["program"])
    shapes = [c[0] for c in r.kernel_events(d["program"])]
    assert shapes and all(len(s) == 3 and s[2] == 128 for s in shapes)
    assert r.device_ops() and all(v > 0 for _, v in r.device_ops())
    for gap_name, seconds in r.idle_gaps():
        assert isinstance(gap_name, str) and seconds >= 0


def test_peaks_and_flash_cost():
    p = roofline.peaks("TPU v5 lite")
    assert p == {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    # causal pairs: 4096*4097/2; forward is 2 matmuls of 2*pairs*d each
    ops, nbytes = roofline.flash_cost(96, 4096, 4096, 128, 12, True, 4096, 2)
    assert ops == 2 * 2 * 96 * (4096 * 4097 // 2) * 128
    assert nbytes == 2 * 96 * 4096 * 128 * 2 + 2 * 8 * 4096 * 128 * 2 + 96 * 4096 * 4
    assert roofline.attended_pairs(8, 8, True, 3) == 21
    assert roofline.attended_pairs(4, 10, True, 0) == 34
    assert roofline.attended_pairs(6, 6, False, 0) == 36
    # this call is bound by operations, not bytes
    assert roofline.least_seconds(ops, nbytes, "TPU v5 lite") == ops / 197e12
    assert roofline.least_seconds(1.0, 819e9, "TPU v5 lite") == 1.0
