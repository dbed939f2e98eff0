"""Live telemetry (ISSUE 11): utils/telemetry + the SLO/goodput pipeline.

The decisive properties:

* SKETCH — the log-bucketed histogram reports percentiles within its
  documented relative error against exact nearest-rank, from fixed
  memory, and ``merge`` over shards equals one sketch over the union
  (the satellite-1 cross-check pin).
* REGISTRY — counters sum, gauges keep the max, histogram percentiles
  re-derive from merged counts; the Prometheus exposition is cumulative
  and internally consistent (monotone buckets, ``+Inf`` == count).
* SAMPLER — interval-gated, append-mode JSONL (a restart continues the
  file), a raising source is recorded as an error instead of killing
  the loop, and ``close()`` is idempotent.
* SLO — the engine judges TTFT at first token and TPOT at retirement;
  ``ServingStats`` folds verdicts into met/miss/goodput counters that
  stay exact under the bounded reservoir and sum under ``merge`` — all
  the way through a router failover, where the killed replica stays
  visible in the sampler's time-series with a frozen heartbeat.
"""

import json
import math
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    Router,
    ServingStats,
    slo_verdict,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.scheduler import Request
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.telemetry import (
    HistogramSketch,
    MetricsRegistry,
    RollingHistogram,
    Telemetry,
)

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [9, 1], [3, 3, 3, 3]]


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _exact_pct(vals, q):
    """Nearest-rank percentile, the definition the sketch approximates."""
    s = sorted(vals)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# ----------------------------------------------------------------------
# histogram sketch


def test_sketch_vs_exact_percentiles():
    """Satellite-1 pin: on 5000 lognormal latencies the sketch's
    p50/p95/p99 are within the growth-factor relative error of exact
    nearest-rank — the bound docs/OBSERVABILITY.md promises."""
    rng = random.Random(0)
    vals = [rng.lognormvariate(-3.0, 1.0) for _ in range(5000)]
    s = HistogramSketch()  # growth 1.1 -> ~10% relative error
    for v in vals:
        s.record(v)
    assert s.count == len(vals)
    assert s.sum == pytest.approx(sum(vals))
    for q in (50, 95, 99):
        exact = _exact_pct(vals, q)
        assert s.percentile(q) == pytest.approx(exact, rel=0.11), q
    # extreme ranks clamp to the exact observed range, never invent
    assert min(vals) <= s.percentile(0) <= max(vals)
    assert s.percentile(100) == pytest.approx(max(vals), rel=0.11)


def test_sketch_merge_equals_union_and_roundtrip():
    """merge(shards) == one sketch over the union (the ServingStats.merge
    discipline: percentiles from merged counts, not averaged), and the
    to_dict dump survives a strict-JSON round trip losslessly."""
    rng = random.Random(1)
    vals = [rng.lognormvariate(-2.0, 0.7) for _ in range(2000)]
    whole, a, b = HistogramSketch(), HistogramSketch(), HistogramSketch()
    for i, v in enumerate(vals):
        whole.record(v)
        (a if i % 2 else b).record(v)
    merged = HistogramSketch.merge([a, b])
    assert merged.count == whole.count
    assert merged.min == whole.min and merged.max == whole.max
    for q in (50, 95, 99):
        assert merged.percentile(q) == whole.percentile(q)

    dump = json.loads(json.dumps(whole.to_dict(), allow_nan=False))
    back = HistogramSketch.from_dict(dump)
    assert back.percentiles() == whole.percentiles()
    assert back.count == whole.count and back.sum == pytest.approx(whole.sum)

    with pytest.raises(ValueError, match="different bucket configs"):
        a.merge_from(HistogramSketch(growth=1.5))


def test_sketch_edges_nonfinite_and_bounds():
    s = HistogramSketch(lo=1e-3, hi=10.0)
    assert s.percentile(50) is None  # empty
    s.record(float("nan"))
    s.record(float("inf"))
    assert s.nonfinite == 2 and s.count == 0  # never poison a percentile
    s.record(1e-9)   # underflow
    s.record(0.0)    # zero lands in underflow too
    s.record(100.0)  # overflow
    assert s.underflow == 2 and s.overflow == 1
    # out-of-range regions report the exact observed extremes
    assert s.percentile(1) == 0.0
    assert s.percentile(100) == 100.0
    assert s.min == 0.0 and s.max == 100.0
    with pytest.raises(ValueError, match="in \\[0, 100\\]"):
        s.percentile(101)
    with pytest.raises(ValueError, match="lo"):
        HistogramSketch(lo=0.0)


def test_rolling_window_tracks_recent_lifetime_keeps_all():
    """After the window rotates past the early samples, window
    percentiles see ONLY the recent regime while lifetime keeps both —
    the regression-visibility property the sampler's window_p99 buys."""
    h = RollingHistogram(window=3)
    for _ in range(50):
        h.record(0.001)
    for _ in range(3):  # rotate the slow burst out of the window
        h.rotate()
    for _ in range(50):
        h.record(1.0)
    w, lt = h.window_sketch(), h.lifetime
    assert w.count == 50 and lt.count == 100
    assert w.percentile(50) == pytest.approx(1.0, rel=0.11)
    assert lt.percentile(99) == pytest.approx(1.0, rel=0.11)
    assert lt.percentile(25) == pytest.approx(0.001, rel=0.11)


# ----------------------------------------------------------------------
# registry


def test_registry_snapshot_and_merge_semantics():
    """Counters SUM, gauges MAX, histogram percentiles re-derive from
    merged sketches; everything strict-JSON."""
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("tokens", 10)
    b.inc("tokens", 5)
    b.inc("only_b")
    a.set_gauge("depth", 3)
    b.set_gauge("depth", 7)
    b.set_gauge("label", "x")  # non-numeric gauge: dropped from merge
    for v in (0.01, 0.02, 0.03):
        a.observe("lat", v)
    b.observe("lat", 0.04)

    snap = a.snapshot()
    assert snap["counters"]["tokens"] == 10
    assert snap["histograms"]["lat"]["count"] == 3
    assert snap["histograms"]["lat"]["window_count"] == 3
    json.loads(json.dumps(snap, allow_nan=False))

    m = MetricsRegistry.merge([a.to_dict(), b.to_dict()])
    assert m["n_sources"] == 2
    assert m["counters"] == {"tokens": 15, "only_b": 1}
    assert m["gauges"]["depth"] == 7
    assert "label" not in m["gauges"]
    assert m["histograms"]["lat"]["count"] == 4
    assert m["histograms"]["lat"]["min"] == 0.01
    assert m["histograms"]["lat"]["max"] == 0.04
    assert m["histograms"]["lat"]["p50"] == pytest.approx(0.02, rel=0.11)
    json.loads(json.dumps(m, allow_nan=False))


def test_prometheus_exposition_consistency():
    """Typed counter/gauge lines; histogram buckets CUMULATIVE and
    monotone with le='+Inf' == count (underflow folds into the first
    emitted bucket, overflow appears in +Inf only); bool extra gauges
    emit as 0/1 and non-finite values are skipped."""
    r = MetricsRegistry()
    r.inc("reqs", 3)
    r.set_gauge("depth", 2)
    for v in (1e-9, 0.01, 0.02, 0.5, 1e6):  # under + 3 in-range + over
        r.observe("lat", v)
    text = r.to_prometheus(prefix="dtm",
                           extra_gauges={"up": True,
                                         "bad": float("nan")})
    lines = text.splitlines()
    assert "# TYPE dtm_reqs counter" in lines and "dtm_reqs 3" in lines
    assert "# TYPE dtm_depth gauge" in lines and "dtm_depth 2" in lines
    assert "dtm_up 1" in lines
    assert not any(ln.startswith("dtm_bad") for ln in lines)

    cums, les = [], []
    for ln in lines:
        if ln.startswith("dtm_lat_bucket{le="):
            le = ln.split('le="')[1].split('"')[0]
            cums.append(int(ln.rsplit(" ", 1)[1]))
            if le != "+Inf":
                les.append(float(le))
    assert cums == sorted(cums), "buckets must be cumulative"
    assert les == sorted(les), "le bounds must ascend"
    assert cums[0] >= 2, "underflow folds into the first emitted bucket"
    assert cums[-1] == 5, "le=+Inf must equal the total count"
    assert "dtm_lat_count 5" in lines


# ----------------------------------------------------------------------
# sampler


def test_sampler_interval_jsonl_append_prom_and_sick_source(tmp_path):
    clock = _Clock()
    jsonl = tmp_path / "t.jsonl"
    prom = tmp_path / "t.prom"

    def boom():
        raise RuntimeError("sick")

    tel = Telemetry(interval_s=1.0, jsonl_path=str(jsonl),
                    prom_path=str(prom), clock=clock)
    tel.register_source("good", lambda: {"depth": 4, "ok": True})
    tel.register_source("bad", boom)
    tel.inc("reqs", 2)
    tel.observe("lat", 0.02)

    rec = tel.maybe_sample()          # first call always samples
    assert rec is not None and rec["sample"] == 0
    assert rec["sources"]["good"]["depth"] == 4
    assert rec["sources"]["bad"] == {"error": "RuntimeError: sick"}
    assert tel.source_errors == 1     # recorded, loop alive
    clock.t += 0.5
    assert tel.maybe_sample() is None  # not due
    clock.t += 0.6
    assert tel.maybe_sample() is not None

    prom_text = prom.read_text()
    assert "dtm_src_good_depth 4" in prom_text
    assert "dtm_src_good_ok 1" in prom_text  # bools flatten to 0/1
    assert "dtm_reqs 2" in prom_text

    tel.close()                       # final sample, then closed
    tel.close()                       # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        tel.sample()
    assert tel.maybe_sample() is None

    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert len(lines) == 3            # 2 live samples + close()'s final
    assert [r["sample"] for r in lines] == [0, 1, 2]
    assert all(r["t"] >= lines[0]["t"] for r in lines)

    # APPEND mode: a restarted run continues the same file
    with Telemetry(interval_s=1.0, jsonl_path=str(jsonl), clock=clock) as t2:
        t2.sample()
    assert len(jsonl.read_text().splitlines()) == 5  # +sample +close

    with pytest.raises(ValueError, match="interval_s"):
        Telemetry(interval_s=0)


def test_sampler_source_replace_and_heartbeat():
    """register_source REPLACES by name (respawn semantics) and
    heartbeat() stamps a clock gauge a stalled component stops moving."""
    clock = _Clock(t=7.0)
    tel = Telemetry(interval_s=1.0, clock=clock)
    tel.register_source("engine0", lambda: {"gen": 1})
    tel.register_source("engine0", lambda: {"gen": 2})  # the respawn
    tel.heartbeat("worker")
    rec = tel.sample()
    assert rec["sources"]["engine0"] == {"gen": 2}
    assert rec["gauges"]["worker_heartbeat_t"] == 7.0
    with pytest.raises(ValueError, match="callable"):
        tel.register_source("nope", 42)


# ----------------------------------------------------------------------
# SLO verdicts + bounded stats reservoir


def _req(i, status="done", ttft=None, tpot=None, ttft_ok=None,
         tpot_ok=None, submit_t=0.0, first=1.0, finish=2.0, gen=3):
    r = Request(id=i, tokens=np.array([1, 2], np.int32), max_new=4,
                bucket=8, deadline_s=None, submit_t=submit_t,
                ttft_slo_s=ttft, tpot_slo_s=tpot)
    r.status = status
    r.admit_t = submit_t + 0.1
    r.first_token_t = first
    r.finish_t = finish
    r.generated = list(range(gen))
    r.slo_ttft_ok = ttft_ok
    r.slo_tpot_ok = tpot_ok
    return r


def test_slo_verdict_rules():
    assert slo_verdict(_req(0)) is None                 # no SLO declared
    assert slo_verdict(_req(1, ttft=1.0, ttft_ok=True)) == "met"
    assert slo_verdict(_req(2, ttft=1.0, ttft_ok=False)) == "miss"
    assert slo_verdict(_req(3, ttft=1.0, tpot=1.0, ttft_ok=True,
                            tpot_ok=False)) == "miss"
    # a declared SLO on a request that never finished is a MISS — failed
    # and cancelled work is not goodput
    assert slo_verdict(_req(4, status="failed", ttft=1.0)) == "miss"
    assert slo_verdict(_req(5, status="cancelled", tpot=1.0)) == "miss"


def test_stats_reservoir_bounds_memory_counters_stay_exact():
    """sample_cap bounds the per-request list (uniform reservoir) while
    every counter-derived summary figure stays EXACT; merge sums the
    counters from counters, not from the surviving samples."""
    st = ServingStats(slots=2, sample_cap=8)
    for i in range(100):
        st.add(_req(i, status=("done" if i % 4 else "failed"),
                    ttft=1e4, ttft_ok=(True if i % 4 else None),
                    submit_t=float(i), first=i + 0.5, finish=i + 1.0))
    assert len(st.requests) == 8          # bounded, not 100
    s = st.summary()
    assert s["sample_cap"] == 8 and s["percentile_samples"] == 8
    assert s["n_requests"] == 100         # exact from counters
    assert s["n_done"] == 75 and s["n_failed"] == 25
    assert s["tokens_generated"] == 300
    assert s["slo_tracked"] == 100
    assert s["slo_met"] == 75 and s["slo_miss"] == 25
    assert s["slo_met_rate"] == 0.75
    assert s["goodput_rps"] is not None
    json.loads(json.dumps(s, allow_nan=False))

    other = ServingStats(slots=2, sample_cap=8)
    other.add(_req(0, ttft=1e4, ttft_ok=True))
    m = ServingStats.merge([st, other])
    assert m["n_requests"] == 101 and m["slo_tracked"] == 101
    assert m["slo_met"] == 76 and m["slo_miss"] == 25
    assert m["percentile_samples"] == 9   # union of the reservoirs
    json.loads(json.dumps(m, allow_nan=False))
    with pytest.raises(ValueError, match="sample_cap"):
        ServingStats(slots=1, sample_cap=0)


def test_scheduler_validates_slo_params():
    sch = FIFOScheduler(max_len=256)
    with pytest.raises(ValueError, match="ttft_slo_s"):
        sch.submit([1, 2], max_new=2, ttft_slo_s=0.0)
    with pytest.raises(ValueError, match="tpot_slo_s"):
        sch.submit([1, 2], max_new=2, tpot_slo_s=-1.0)
    r = sch.submit([1, 2], max_new=2, ttft_slo_s=0.5, tpot_slo_s=0.1)
    assert r.ttft_slo_s == 0.5 and r.tpot_slo_s == 0.1


# ----------------------------------------------------------------------
# engine wiring


def test_engine_judges_slos_and_feeds_the_sampler(tmp_path):
    """A generous SLO is met, an impossible one misses at first token,
    an SLO-less request stays untracked; the wired sampler sees the
    engine's vitals and the TTFT histogram, and the Prometheus file
    carries the per-source SLO counters."""
    model, params = _model_and_params()
    prom = tmp_path / "e.prom"
    tel = Telemetry(interval_s=1e9, prom_path=str(prom))  # manual samples
    eng = InferenceEngine(model, params, slots=2, max_len=16,
                          telemetry=tel,
                          scheduler=FIFOScheduler(max_len=16, buckets=(8,)))
    ok = eng.submit(PROMPTS[0], max_new=4, ttft_slo_s=1e4, tpot_slo_s=1e4)
    bad = eng.submit(PROMPTS[1], max_new=4, ttft_slo_s=1e-9)
    free = eng.submit(PROMPTS[2], max_new=4)
    eng.run()
    assert ok.slo_ttft_ok is True and ok.slo_tpot_ok is True
    assert bad.slo_ttft_ok is False
    assert free.slo_ttft_ok is None and all(
        r.status == "done" for r in (ok, bad, free))
    s = eng.stats.summary()
    assert s["slo_tracked"] == 2
    assert s["slo_met"] == 1 and s["slo_miss"] == 1
    assert s["slo_ttft_miss"] == 1 and s["slo_tpot_miss"] == 0

    rec = tel.sample()
    v = rec["sources"]["engine0"]
    assert v["slo_met"] == 1 and v["slo_miss"] == 1
    assert v["queue_depth"] == 0 and v["occupied_slots"] == 0
    assert v["last_progress_t"] is not None
    assert rec["histograms"]["ttft_s"]["count"] == 3
    assert rec["counters"]["tokens_generated"] == s["tokens_generated"]
    text = prom.read_text()
    assert "dtm_src_engine0_slo_met 1" in text
    assert "dtm_ttft_s_bucket" in text
    eng.close()


def test_slo_counters_exact_on_an_overloaded_queue():
    """4x-slots requests queued at once (the queue is the overload), half
    with a TTFT SLO below one jit dispatch and half with one nothing can
    miss, then an unloaded wave that fits the slots.  Arithmetic, not
    timing: met + miss == tracked on each leg, the tight half misses at
    first token exactly, the generous half and the unloaded leg meet
    exactly, goodput is reported, and ``ServingStats.merge`` sums them as
    the router's rollup does."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import ServingStats

    model, params = _model_and_params()
    slots = 2
    n, n_tight = 4 * slots, 2 * slots
    eng = InferenceEngine(
        model, params, slots=slots, max_len=16, decode_ahead=4,
        scheduler=FIFOScheduler(max_len=16, buckets=(8,), max_queue=n))
    for i in range(n):
        eng.submit(PROMPTS[i % len(PROMPTS)], max_new=4, tpot_slo_s=1e4,
                   ttft_slo_s=(1e-6 if i % 2 == 0 else 1e4))
    eng.run()
    over_stats, over = eng.stats, eng.stats.summary()
    eng.stats = ServingStats(slots, decode_ahead=eng.decode_ahead)
    for p in PROMPTS[:slots]:
        eng.submit(p, max_new=4, ttft_slo_s=1e4, tpot_slo_s=1e4)
    eng.run()
    un = eng.stats.summary()
    merged = ServingStats.merge([over_stats, eng.stats])
    eng.close()
    assert over["slo_met"] + over["slo_miss"] == over["slo_tracked"] == n
    assert over["slo_miss"] == over["slo_ttft_miss"] == n_tight
    assert over["slo_met"] == n - n_tight
    assert un["slo_met"] == un["slo_tracked"] == slots
    assert un["slo_miss"] == 0
    assert over["goodput_rps"] is not None and un["goodput_rps"] is not None
    assert merged["slo_tracked"] == n + slots
    assert merged["slo_met"] == over["slo_met"] + un["slo_met"]
    assert merged["slo_miss"] == over["slo_miss"]


def test_engine_without_telemetry_is_untouched():
    """The nil-guard off-path: no telemetry attribute consulted beyond
    `is not None`, identical serving behavior, SLO judgment still runs
    (accounting is part of the request record, not the sampler)."""
    model, params = _model_and_params()
    eng = InferenceEngine(model, params, slots=2, max_len=16,
                          scheduler=FIFOScheduler(max_len=16, buckets=(8,)))
    r = eng.submit(PROMPTS[0], max_new=4, ttft_slo_s=1e4)
    eng.run()
    assert r.status == "done" and r.slo_ttft_ok is True
    assert eng.stats.summary()["slo_met"] == 1
    eng.close()


# ----------------------------------------------------------------------
# router failover: merged SLO counters + dead-replica visibility


def test_router_failover_merges_slo_and_keeps_dead_replica_visible():
    """Chaos kills one replica mid-wave under all-generous SLOs.  The
    dead attempts (engine_fault collateral) are tracked MISSES in the
    cluster rollup, every re-dispatched attempt is a MET, and the
    sampler's next record still shows the killed replica — state
    'failed', heartbeat frozen, not vanished from the dict."""
    model, params = _model_and_params()
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-step", kind="transient", at=(1,)),)))
    tel = Telemetry(interval_s=1e9)

    def factory(tid):
        return InferenceEngine(
            model, params, slots=2, max_len=16, chaos=inj,
            stall_timeout_s=None, telemetry=tel, trace_tid=tid,
            scheduler=FIFOScheduler(max_len=16, buckets=(8,), max_queue=16))

    r = Router(factory, 2, telemetry=tel)
    rrs = [r.submit(p, max_new=6, ttft_slo_s=1e4, tpot_slo_s=1e4)
           for p in PROMPTS]
    r.run_until_done()
    assert all(rr.status == "done" for rr in rrs)
    assert r.failovers == 1
    moved = [rr for rr in rrs if rr.redispatches]
    assert moved

    summ = r.summary()
    assert summ["slo_tracked"] == len(PROMPTS) + len(moved)
    assert summ["slo_met"] == len(PROMPTS)       # every final attempt
    assert summ["slo_miss"] == len(moved)        # every dead attempt
    assert summ["slo_met_rate"] == pytest.approx(
        len(PROMPTS) / (len(PROMPTS) + len(moved)), abs=1e-4)
    assert summ["goodput_rps"] is not None
    json.loads(json.dumps(summ, allow_nan=False))

    rec = tel.sample()
    reps = rec["sources"]["router"]["replicas"]
    dead = [v for v in reps.values() if v["state"] == "failed"]
    assert len(dead) == 1 and len(reps) == 2
    assert dead[0]["alive"] is False and dead[0]["load"] is None
    assert dead[0]["heartbeat_t"] is not None    # frozen, still visible
    assert rec["sources"]["router"]["failovers"] == 1
    r.close()


# ----------------------------------------------------------------------
# trainer wiring


def test_trainer_heartbeats_and_reports_vitals():
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    tel = Telemetry(interval_s=1e9)  # sample manually at the end
    cfg = RunConfig(model="mlp", model_kwargs={"hidden": (32,)},
                    synthetic=True, n_train=256, n_test=64, batch_size=64,
                    epochs=2, dp=1, quiet=True)
    with Trainer(cfg, telemetry=tel) as t:
        t.fit()
    rec = tel.sample()
    v = rec["sources"]["trainer"]
    assert v["epochs_done"] == 2
    assert v["weight_step"] == t.steps_per_epoch * 2
    assert rec["gauges"]["trainer_step"] == v["weight_step"]
    assert rec["gauges"]["trainer_heartbeat_t"] > 0


# ----------------------------------------------------------------------
# telemetry_report


def test_telemetry_report_analyze_and_cli(tmp_path, capsys):
    import scripts.telemetry_report as tr

    clock = _Clock(t=10.0)
    jsonl = tmp_path / "run.jsonl"
    vit = {"queue_depth": 2, "slo_tracked": 4, "slo_met": 3, "slo_miss": 1}
    tel = Telemetry(interval_s=1.0, jsonl_path=str(jsonl), clock=clock)
    tel.register_source("engine0", lambda: dict(vit))
    for i in range(3):
        tel.inc("tokens", 10)
        tel.observe("lat", 0.01 * (i + 1))
        tel.sample()
        clock.t += 2.0
        vit["queue_depth"] += 2
    tel.close()

    records, problems = tr.load_records(str(jsonl))
    assert not problems
    rep = tr.analyze(records)
    assert rep["n_samples"] == 4  # 3 + close()'s final
    assert rep["sources"] == ["engine0"]
    c = rep["counters"]["tokens"]
    assert c["first"] == 10 and c["last"] == 30
    assert c["rate_per_s"] == pytest.approx(20 / rep["span_s"], abs=1e-3)
    g = rep["gauges"]["engine0.queue_depth"]
    assert g["min"] == 2 and g["max"] == 8 and g["last"] == 8
    assert rep["histograms"]["lat"]["count"] == 3
    assert rep["slo"]["tracked"] == 4 and rep["slo"]["met"] == 3
    assert rep["slo"]["met_rate"] == 0.75
    assert rep["slo"]["goodput_rps"] is not None

    assert tr.main([str(jsonl), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["n_samples"] == 4 and out["problems"] == []

    # --strict flags garbage lines and non-monotonic time
    bad = tmp_path / "bad.jsonl"
    bad.write_text('not json\n{"t": 5.0, "sample": 0}\n{"t": 1.0}\n')
    assert tr.main([str(bad), "--strict"]) == 1
    assert tr.main([str(bad)]) == 0  # tolerant mode still reports
    capsys.readouterr()


def test_registry_merge_matches_router_rollup_shape():
    """The registry merge is usable as a cross-replica rollup: two
    engine-side registries dumped and merged give cluster totals with
    percentiles over the union — mirroring ServingStats.merge."""
    regs = [MetricsRegistry(), MetricsRegistry()]
    for k, reg in enumerate(regs):
        for i in range(20):
            reg.observe("ttft_s", 0.01 * (i + 1) * (k + 1))
        reg.inc("tokens_generated", 100 * (k + 1))
    m = MetricsRegistry.merge([r.to_dict() for r in regs])
    assert m["counters"]["tokens_generated"] == 300
    assert m["histograms"]["ttft_s"]["count"] == 40
    # union p99 lands near the slow replica's tail, not the average
    assert m["histograms"]["ttft_s"]["p99"] == pytest.approx(0.4, rel=0.12)
