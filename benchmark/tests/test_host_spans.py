"""The readers of the program's host spans (``benchmark/host_spans.py`` and
the seven "serving host loop" metrics over it): on a trace made by hand,
whose every number can be checked on paper; on a slice of this PR's traced
chip run of ``sc2-3b.gen-closed`` (``data/events_serve_spans.json``, every
event of 14 engine iterations); and on the two older fixtures, which hold no
span of the program and must read as nothing.

    python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness, host_spans, trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
METRICS = ("step_host_ms", "prefill_dispatch_host_ms", "first_pick_wait_ms",
           "idle_in_admit_share", "idle_in_window_share", "idle_in_emit_share",
           "idle_outside_step_share")
SHARES = METRICS[3:]


def read(metric, reduced):
    reading = harness.Reading(metric + ".closed", None, {}, reduced, {})
    return harness.find_reader(reading.metric)(reading)


def by_hand():
    """The chip is busy 0-10, 14-24, 30-40 and 41-50 ms of a 60 ms window:
    gaps of 4, 6 and 1 ms.  Three engine iterations: A (9-25 ms) admits one
    request and dispatches a window, B (26-41.5) dispatches a window, C
    (45-46) finds nothing to do; the client has 25-26 to itself."""
    ops = [["fusion.1", "fusion", [4, 8], s * MS, (e - s) * MS, False]
           for s, e in ((0, 10), (14, 24), (30, 40), (41, 50))]

    def span(name, start, end, thread="python3"):
        return [thread, name, int(start * MS), int((end - start) * MS)]

    host = [
        span("_bench:engine.step", 8.9, 25.1),
        span("engine.step", 9, 25), span("engine.admit", 9.5, 12),
        span("site:prefill[b512]", 9.6, 11.6), span("engine.land", 11.6, 12),
        span("engine.first_pick", 11.7, 11.9),
        span("engine.dispatch", 12, 13.5), span("engine.overlap", 13.5, 14.5),
        span("site:prefill[b1024]", 13.6, 14.4),
        span("engine.readback", 14.5, 24.2), span("engine.emit", 24.2, 24.8),
        span("engine.reset", 24.8, 25),
        span("engine.step", 26, 41.5), span("engine.admit", 26, 26.1),
        span("engine.dispatch", 26.1, 30.5), span("engine.overlap", 30.5, 30.5),
        span("engine.readback", 30.5, 40.2), span("engine.emit", 40.2, 40.6),
        span("engine.reset", 40.6, 41.5),
        span("engine.step", 45, 46), span("engine.admit", 45, 45.1),
        span("engine.reset", 45.9, 46),
        span("np.asarray(jax.Array)", 14.6, 24.1),
        span("Execute", 0, 60, thread="main/1"),
    ]
    return tr.Events(devices=[{"id": 0, "modules": [], "ops": ops}], host=host)


def test_spans_are_found_by_name_and_by_prefix():
    r = tr.Reduced(by_hand(), 1, 0.060)
    assert host_spans.spans(r, "engine.step") == [
        (9 * MS, 25 * MS), (26 * MS, int(41.5 * MS)), (45 * MS, 46 * MS)]
    assert len(host_spans.spans(r, "site:prefill[b", prefix=True)) == 2
    assert host_spans.spans(r, "site:prefill[b") == []
    assert host_spans.spans(None, "engine.step") == []
    # C dispatched no window: it is no iteration of the decode loop
    assert host_spans.stepping(r) == [(9 * MS, 25 * MS), (26 * MS, int(41.5 * MS))]


def test_durations_by_hand():
    r = tr.Reduced(by_hand(), 1, 0.060)
    assert read("step_host_ms", r) == pytest.approx(15.75)       # 16 and 15.5
    assert read("prefill_dispatch_host_ms", r) == pytest.approx(1.4)  # 2 and 0.8
    assert read("first_pick_wait_ms", r) == pytest.approx(0.2)


def test_idle_partition_by_hand():
    r = tr.Reduced(by_hand(), 1, 0.060)
    assert host_spans.idle_gaps(r) == [
        (10 * MS, 14 * MS), (24 * MS, 30 * MS), (40 * MS, 41 * MS)]
    parts = host_spans.idle_partition(r)
    # 10-12 admit, 12-14 dispatch and overlap; 24-24.2 readback, 24.2-25
    # emit and reset, 25-26 nobody, 26-26.1 admit, 26.1-30 dispatch;
    # 40-40.2 readback, 40.2-41 emit and reset
    assert parts == {"admit": pytest.approx(0.0021), "window": pytest.approx(0.0063),
                     "emit": pytest.approx(0.0016),
                     "outside_step": pytest.approx(0.0010)}
    assert sum(parts.values()) == pytest.approx(0.011)
    assert read("idle_in_admit_share", r) == pytest.approx(3.5)
    assert read("idle_in_window_share", r) == pytest.approx(10.5)
    assert read("idle_in_emit_share", r) == pytest.approx(100 * 0.0016 / 0.060)
    assert read("idle_outside_step_share", r) == pytest.approx(100 * 0.0010 / 0.060)
    # what device_idle_share reads, less the lead-in (none) and lead-out (50-60)
    idle = 100.0 * (1.0 - r.busy_s / r.window_s)
    assert sum(read(m, r) for m in SHARES) == pytest.approx(idle - 100 * 10 / 60)


def test_intersect():
    assert host_spans.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert host_spans.intersect([(0, 10)], []) == []


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_is_none(metric):
    """Untraced; a trace of a program without the spans (an earlier commit:
    the two older fixtures); spans without a device (the CPU rehearsal)."""
    assert read(metric, None) is None
    for name in ("events_serve.json", "events_train.json"):
        d = json.load(open(os.path.join(HERE, "data", name)))
        assert read(metric, tr.Reduced(tr.Events.from_json(d), 1, d["window_s"])) is None
    ev = by_hand()
    ev.devices = []
    value = read(metric, tr.Reduced(ev, 0, 0.060))
    assert (value is None) == (metric in SHARES)


RECORDED = os.path.join(HERE, "data", "events_serve_spans.json")


@pytest.fixture(scope="module")
def recorded():
    d = json.load(open(RECORDED))
    return d, tr.Reduced(tr.Events.from_json(d), 1, d["window_s"])


def test_recorded_slice_holds_every_span_nested(recorded):
    d, r = recorded
    steps = host_spans.spans(r, "engine.step")
    assert len(steps) == d["expect"]["steps"] == len(host_spans.stepping(r))
    for name in ("engine.admit", "engine.land", "engine.first_pick",
                 "engine.dispatch", "engine.overlap", "engine.readback",
                 "engine.emit", "engine.reset"):
        found = host_spans.spans(r, name)
        assert found, name
        assert all(any(s <= a and b <= e for s, e in steps) for a, b in found), name
    assert host_spans.spans(r, "site:prefill[b", prefix=True)
    assert host_spans.spans(r, "site:decode_window[k1]")
    # the renamed programs are in the device's module line under their names
    programs = {tr.program_of(m[0]) for m in r.devices[0]["modules"]}
    assert {"_window_impl", "_prefill_row", "_insert_row"} <= programs
    assert not [p for p in programs if "lambda" in p]


@pytest.mark.parametrize("metric", METRICS)
def test_recorded_slice_values(recorded, metric):
    d, r = recorded
    assert read(metric, r) == pytest.approx(d["expect"][metric], rel=1e-9)


def test_recorded_slice_partition(recorded):
    d, r = recorded
    parts = host_spans.idle_partition(r)
    gaps = host_spans.idle_gaps(r)
    assert sum(parts.values()) == pytest.approx(tr.length(gaps) / 1e9, rel=1e-12)
    assert all(v >= 0 for v in parts.values())
    # the slice starts and ends on a step's edge, so nearly all of what
    # device_idle_share counts lies between the first and last operation
    idle = 100.0 * (1.0 - r.busy_s / r.window_s)
    assert sum(read(m, r) for m in SHARES) == pytest.approx(idle, abs=0.5)
    # the gap namer now finds the program's spans
    names = {name for name, _s in r.idle_gaps(top=20)}
    assert any(n.startswith(("python3:engine.", "python3:site:")) for n in names), names
