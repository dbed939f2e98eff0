"""End-to-end tracing + compile accounting (ISSUE 6, utils/tracing.py).

The decisive properties:

* EXPORT VALIDITY — a chaos-enabled serving soak exports STRICT
  Chrome-trace JSON: every span closed, every parent resolving, no
  NaN/Infinity tokens (``validate_trace`` is the mechanical check, and
  the tests also pin what it checks).
* CORRELATION — each request's root span duration matches its reported
  latency (one shared monotonic clock), and injected chaos faults attach
  to the requests they actually hit.
* COMPILE ACCOUNTING — ``CompileTracker`` counts only programs actually
  compiled (repeats are cache hits: zero), attributed to the site that
  triggered them.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
    CompileTracker,
    Tracer,
    load_trace,
    validate_trace,
)

KW = dict(num_classes=16, dim=64, depth=2, heads=4, dtype=jnp.float32)


def _model_and_params(seed=0):
    model = get_model("causal_lm", **KW)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _spans(doc):
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


# ----------------------------------------------------------------------
# Tracer unit behaviour


def test_tracer_span_tree_counters_and_summary():
    clock = iter(np.arange(0.0, 10.0, 0.125))
    tr = Tracer(clock=lambda: float(next(clock)))
    root = tr.begin("request", cat="serving", req=7)
    child = tr.begin("queue", cat="serving", parent=root)
    tr.end(child)
    with tr.span("decode", cat="serving", parent=root, slot=1):
        tr.instant("first_token", cat="serving", parent=root, slot=1)
    tr.counter("queue_depth", 3)
    tr.end(root, status="done")
    assert tr.open_spans == 0 and tr.dropped == 0

    events = tr.events()
    assert [e["name"] for e in events] == [
        "queue", "first_token", "decode", "queue_depth", "request"]
    req = events[-1]
    assert req["args"]["req"] == 7 and req["args"]["status"] == "done"
    # children closed before the root carry its id as parent
    assert events[0]["parent"] == req["id"]

    s = tr.summary()
    assert s["events"] == len(events) and s["open_spans"] == 0
    assert s["phases"]["serving/request"]["n"] == 1
    assert s["phases"]["serving/decode"]["total_s"] > 0
    assert s["counters"]["queue_depth"] == 3.0
    json.dumps(s, allow_nan=False)  # strict-JSON clean


def test_tracer_end_of_unknown_span_is_ignored():
    tr = Tracer()
    tr.end(12345)  # never began: must not raise (retirement races)
    sid = tr.begin("x")
    tr.end(sid)
    tr.end(sid)  # double end: second is a no-op
    assert tr.open_spans == 0 and len(tr.events()) == 1


def test_tracer_ring_bound_drops_closed_never_open():
    tr = Tracer(capacity=8)
    root = tr.begin("request")  # open: must survive any overflow
    for i in range(50):
        tr.instant("tick", i=i)
    assert len(tr.events()) == 8 and tr.dropped == 42
    tr.end(root, status="done")  # still closable after the wrap
    assert tr.open_spans == 0
    assert tr.summary()["dropped"] == 43  # the close evicted one more tick
    # the root landed even though the instants around it were evicted
    assert tr.events()[-1]["name"] == "request"


def test_export_strict_json_validates_and_names_tracks(tmp_path):
    tr = Tracer()
    tid = tr.track("req 0")
    root = tr.begin("request", cat="serving", tid=tid, req=0)
    with tr.span("decode", cat="serving", parent=root, tid=tid):
        pass
    tr.end(root, status="done")
    tr.counter("queue_depth", 0)
    path = tmp_path / "t.trace.json"
    out = tr.export_trace(str(path))
    assert out["events"] > 0 and out["path"] == str(path)

    assert validate_trace(str(path)) == []
    doc = load_trace(str(path))
    assert doc["displayTimeUnit"] == "ms"
    names = {(e["ph"], e.get("name")) for e in doc["traceEvents"]}
    assert ("M", "thread_name") in names and ("C", "queue_depth") in names
    spans = _spans(doc)
    ids = [e["args"]["id"] for e in spans]
    assert len(ids) == len(set(ids)) == 2
    # the child's parent resolves to the root's exported id
    by_name = {e["name"]: e for e in spans}
    assert by_name["decode"]["args"]["parent"] == by_name["request"]["args"]["id"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in spans)


def test_export_flags_open_spans_and_validator_rejects(tmp_path):
    tr = Tracer()
    tr.begin("request", req=1)  # never ended
    path = tmp_path / "open.trace.json"
    tr.export_trace(str(path))
    doc = load_trace(str(path))
    assert any(e["ph"] == "B" for e in doc["traceEvents"])
    problems = validate_trace(str(path))
    assert problems and any("unclosed" in p for p in problems)


def test_export_drops_dangling_parent_refs(tmp_path):
    """A child whose parent was ring-evicted exports WITHOUT the parent
    arg — a wrapped trace still passes parent-resolution validation."""
    tr = Tracer(capacity=2)
    root = tr.begin("request")
    tr.end(root)
    for i in range(5):  # evict the root from the ring
        tr.instant("tick", i=i)
    child = tr.begin("late", parent=root)
    tr.end(child)
    path = tmp_path / "wrap.trace.json"
    tr.export_trace(str(path))
    assert validate_trace(str(path)) == []
    late = [e for e in _spans(load_trace(str(path))) if e["name"] == "late"]
    assert late and "parent" not in late[0]["args"]


def test_load_trace_rejects_nonstrict_json(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"traceEvents": [{"ph": "X", "ts": NaN}]}')
    with pytest.raises(ValueError, match="non-strict"):
        load_trace(str(p))
    assert any("strict" in s or "parse" in s for s in validate_trace(str(p)))


# ----------------------------------------------------------------------
# CompileTracker


def test_compile_tracker_singleton_and_site_attribution():
    tracker = CompileTracker.install()
    assert CompileTracker.install() is tracker  # one per process

    before = tracker.snapshot()
    f = jax.jit(lambda x: x * 2 + 1)
    with tracker.site("test_site_a"):
        f(jnp.arange(7.0)).block_until_ready()
    mid = tracker.snapshot()
    d1 = CompileTracker.delta(mid, before)
    assert d1["n_compiled_programs"] >= 1
    assert "test_site_a" in d1["by_site"]

    # the SAME program again: a tracing-cache hit compiles nothing
    with tracker.site("test_site_b"):
        f(jnp.arange(7.0)).block_until_ready()
    d2 = CompileTracker.delta(tracker.snapshot(), mid)
    assert d2["n_compiled_programs"] == 0 and d2["by_site"] == {}


def test_compile_tracker_bound_tracer_gets_instants():
    tracker = CompileTracker.install()
    tr = Tracer()
    tracker.bind(tr)
    try:
        with tracker.site("bound_site"):
            jax.jit(lambda x: x - 3)(jnp.arange(5.0)).block_until_ready()
    finally:
        tracker.bind(None)
    hits = [e for e in tr.events()
            if e["name"] == "xla_compile" and e["args"]["site"] == "bound_site"]
    assert hits and hits[0]["args"]["compile_time_s"] > 0


# ----------------------------------------------------------------------
# serving integration: the ISSUE 6 acceptance pin


def _traced_engine(model, params, tracer, chaos=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 24)
    return InferenceEngine(
        model, params, chaos=chaos, tracer=tracer,
        scheduler=FIFOScheduler(max_len=kw["max_len"], buckets=(8,)), **kw)


def test_serving_trace_end_to_end_with_chaos(tmp_path):
    """Chaos-enabled serving run -> export -> validate: every span
    closed, parents resolve, strict JSON; each request's root span
    duration matches its reported latency (shared clock); the injected
    fault attaches to the request it hit and no other."""
    model, params = _model_and_params()
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-admit", kind="poison", at=(1,)),
    )))
    tr = Tracer()
    eng = _traced_engine(model, params, tr, chaos=inj, decode_ahead=2,
                         prefix_cache_bytes=16 << 20)
    rng = np.random.default_rng(0)
    reqs = []
    repeat = np.asarray([3, 1, 4, 1, 5], np.int32)  # prefix-cache bait
    for i in range(6):
        prompt = (repeat if i >= 4 else
                  rng.integers(1, 16, size=(2 + i % 4,)).astype(np.int32))
        reqs.append(eng.submit(prompt, max_new=3 + i % 3))
    done = eng.run()
    assert len(done) == 6 and tr.open_spans == 0

    path = tmp_path / "serving.trace.json"
    tr.export_trace(str(path))
    assert validate_trace(str(path)) == []
    doc = load_trace(str(path))
    spans = _spans(doc)
    roots = {e["args"]["req"]: e for e in spans if e["name"] == "request"}
    assert set(roots) == {r.id for r in reqs}

    for r in reqs:
        root = roots[r.id]
        if r.status == "done":
            want_s = r.finish_t - r.submit_t
            assert abs(root["dur"] / 1e6 - want_s) < 0.05, r.id
            # child phases tile the root: queue+admit+decode <= total
            kids = [e for e in spans
                    if e["args"].get("parent") == root["args"]["id"]]
            assert {"queue", "decode"} <= {k["name"] for k in kids}
            assert sum(k["dur"] for k in kids if k["name"] != "prefill"
                       ) <= root["dur"] * 1.02 + 1000
        assert root["args"]["status"] == r.status

    # the fault landed on request 1's track, parented under ITS root
    faults = [e for e in doc["traceEvents"] if e["name"] == "chaos_fault"]
    assert len(faults) == 1
    assert faults[0]["args"]["parent"] == roots[reqs[1].id]["args"]["id"]
    assert faults[0]["args"]["site"] == "serving-admit"
    assert reqs[1].status == "failed"
    assert roots[reqs[1].id]["args"]["status"] == "failed"

    # prefix-cache hit instants attach to the repeated-prompt requests
    hits = [e for e in doc["traceEvents"] if e["name"] == "prefix_cache_hit"]
    assert len(hits) == 1  # req 5 hits what req 4 stored
    assert hits[0]["args"]["parent"] == roots[reqs[5].id]["args"]["id"]

    # stats carry the compile ledger
    s = eng.stats.summary()
    assert s["n_compiled_programs"] >= 1
    assert any(k.startswith("prefill[b8]") for k in s["compile_by_site"])


def test_engine_close_closes_all_request_spans():
    model, params = _model_and_params()
    tr = Tracer()
    eng = _traced_engine(model, params, tr)
    for i in range(4):  # 2 slots: 2 admit, 2 stay queued
        eng.submit(np.asarray([1, 2, 3], np.int32), max_new=4)
    eng.close()
    assert tr.open_spans == 0
    statuses = [e["args"]["status"] for e in tr.events()
                if e["name"] == "request"]
    assert len(statuses) == 4 and set(statuses) == {"cancelled"}


def test_engine_rejects_two_different_tracers():
    model, params = _model_and_params()
    sched = FIFOScheduler(max_len=24, buckets=(8,), tracer=Tracer())
    with pytest.raises(ValueError, match="tracer"):
        InferenceEngine(model, params, slots=2, max_len=24,
                        tracer=Tracer(), scheduler=sched)
    # engine adopts the scheduler's tracer when it has none
    eng = InferenceEngine(model, params, slots=2, max_len=24, scheduler=sched)
    assert eng._tracer is sched.tracer


def test_tracerless_engine_has_no_tracer_state():
    """The nil-guard zero-overhead contract, structurally: no tracer ->
    every site is one attribute test, and no spans exist anywhere."""
    model, params = _model_and_params()
    eng = InferenceEngine(
        model, params, slots=2, max_len=24,
        scheduler=FIFOScheduler(max_len=24, buckets=(8,)))
    assert eng._tracer is None and eng.scheduler.tracer is None
    r = eng.submit(np.asarray([1, 2], np.int32), max_new=3)
    eng.run()
    assert r.trace is None and r.status == "done"


# ----------------------------------------------------------------------
# training integration


def test_trainer_trace_spans_and_compile_summary(tmp_path):
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    tr = Tracer()
    cfg = RunConfig(
        model="mlp", model_kwargs={"hidden": (32,)}, synthetic=True,
        n_train=256, n_test=64, batch_size=64, epochs=2, dp=1, quiet=True,
        eval_every=1, checkpoint_every=1, input_mode="stream",
        stream_chunk=2, checkpoint_dir=str(tmp_path / "ck"),
    )
    t = Trainer(cfg, tracer=tr)
    summary = t.fit()
    assert tr.open_spans == 0
    names = {(e["cat"], e["name"]) for e in tr.events()}
    assert {("train", "epoch_dispatch"), ("train", "fetch"),
            ("train", "eval"), ("train", "h2d"), ("train", "dispatch"),
            ("train", "checkpoint_save")} <= names

    # restore traces too
    t2 = Trainer(cfg.replace(resume=True), tracer=tr)
    step = t2.restore_checkpoint()
    assert step > 0
    restores = [e for e in tr.events() if e["name"] == "checkpoint_restore"]
    assert restores and restores[-1]["args"]["restored_step"] == step

    path = tmp_path / "train.trace.json"
    tr.export_trace(str(path))
    assert validate_trace(str(path)) == []

    # fit summary carries the compile ledger
    assert summary["n_compiled_programs"] >= 1
    assert summary["compile_time_s"] >= 0


def test_elastic_restart_instant_lands_on_timeline(tmp_path):
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector as FI,
        FaultPlan as FP,
        FaultSpec as FS,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
    from distributed_tensorflow_ibm_mnist_tpu.utils.elastic import (
        run_with_recovery,
    )

    tr = Tracer()
    cfg = RunConfig(
        model="mlp", model_kwargs={"hidden": (32,)}, synthetic=True,
        n_train=256, n_test=64, batch_size=64, epochs=2, dp=1, quiet=True,
        checkpoint_every=1, checkpoint_dir=str(tmp_path / "ck"),
        input_mode="stream", stream_chunk=2,
    )
    inj = FI(FP(faults=(FS(site="data-batch", kind="io", at=(3,)),)))
    summary = run_with_recovery(
        lambda: Trainer(cfg, chaos=inj), max_restarts=2,
        backoff_base_s=0.0, tracer=tr)
    assert summary["restarts"] == 1
    restarts = [e for e in tr.events() if e["name"] == "restart"]
    assert len(restarts) == 1
    assert restarts[0]["cat"] == "elastic"
    assert restarts[0]["args"]["exception"] == "OSError"
    assert restarts[0]["args"]["attempt"] == 1
    # the supervised trainer inherited the tracer: the fit spans of every
    # attempt land on the SAME timeline as the restart instant
    assert any(e["name"] == "epoch_dispatch" for e in tr.events())
    assert tr.open_spans == 0


# ----------------------------------------------------------------------
# trace_report


def test_trace_report_analyze_and_cli(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)

    model, params = _model_and_params()
    tr = Tracer()
    eng = _traced_engine(model, params, tr)
    for i in range(3):
        eng.submit(np.asarray([1, 2, 3 + i], np.int32), max_new=3)
    eng.run()
    path = tmp_path / "r.trace.json"
    tr.export_trace(str(path))

    rep = trace_report.analyze(load_trace(str(path)))
    assert rep["n_spans"] > 0
    assert any(p["phase"] == "serving/request" for p in rep["phases"])
    assert len(rep["requests"]) == 3
    for r in rep["requests"]:
        assert r["status"] == "done"
        assert r["total_ms"] >= sum(r["phases_ms"].values()) * 0.98 - 1.0
        assert "decode" in r["phases_ms"]

    # the CLI form: --json emits the same analysis as one strict line
    out = subprocess.run(
        [sys.executable, os.path.join("scripts", "trace_report.py"),
         str(path), "--json", "--strict"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout)
    assert rec["problems"] == [] and len(rec["requests"]) == 3


def test_trace_report_counter_track_rollup(tmp_path):
    """ISSUE 11 satellite: counter tracks roll up to n/min/mean/max/last
    over the recorded CHANGE points (the tracer dedups repeats, so the
    mean is over distinct recorded values, not time-weighted)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)

    tr = Tracer()
    for v in (3, 1, 8, 4):
        tr.counter("queue_depth", v)
    tr.counter("occupied_slots", 2)
    path = tmp_path / "c.trace.json"
    tr.export_trace(str(path))

    rep = trace_report.analyze(load_trace(str(path)))
    q = rep["counter_stats"]["queue_depth.value"]
    assert q["n"] == 4
    assert q["min"] == 1 and q["max"] == 8 and q["last"] == 4
    assert q["mean"] == 4.0
    o = rep["counter_stats"]["occupied_slots.value"]
    assert o["n"] == 1 and o["last"] == 2
    # the legacy last-value map stays for compat
    assert rep["counters_last"]["queue_depth.value"] == 4
    json.loads(json.dumps(rep, allow_nan=False))


# ----------------------------------------------------------------------
# the serving engine's program family, site by site


def test_serving_compile_census_by_site(eight_devices):
    """``n_compiled_programs`` moves when, and only when, a new member of
    the program family appears, and every engine's cold set is pinned site
    by site: one more program under any site is a compile storm at
    start-up or a flapping jit cache key, even when every token is still
    right.  The whole sequence runs twice, first at another width and
    unpinned: that pass compiles the module-level programs (the shared
    ``first_pick``, eager helpers), whose count would otherwise follow
    whatever this process ran before."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import SamplingParams

    tracker = CompileTracker.install()

    def census(dim):
        model = get_model("causal_lm", num_classes=32, dim=dim, depth=1,
                          heads=2, dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(4),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        rng = np.random.default_rng(5)
        legs = {}

        def prompt(n):
            return rng.integers(1, 31, size=(n,)).astype(np.int32)

        def engine(**kw):
            return InferenceEngine(
                model, params, slots=2, max_len=48,
                scheduler=FIFOScheduler(max_len=48, buckets=(16, 32),
                                        max_queue=8), **kw)

        def serve(leg, eng, prompts, sampling=None):
            before = tracker.snapshot()
            reqs = [eng.submit(p, max_new=8, sampling=sampling)
                    for p in prompts]
            eng.run()
            assert all(r.status == "done" for r in reqs)
            d = CompileTracker.delta(tracker.snapshot(), before)
            legs[leg] = {site: rec["n"] for site, rec in d["by_site"].items()
                         if site != "unattributed"}

        eng = engine()
        serve("bucket16_first", eng, [prompt(8)])
        serve("bucket16_repeat", eng, [prompt(10)])
        serve("bucket32_new", eng, [prompt(24)])
        serve("bucket32_repeat", eng, [prompt(28)])
        serve("sample_cold", eng, [prompt(8)], SamplingParams(
            temperature=0.8, top_p=0.9, seed=11))
        serve("sample_repeat", eng, [prompt(10)], SamplingParams(
            temperature=1.1, top_p=0.5, seed=12))
        eng.close()
        # a shared-prefix pair, so the radix suffix-extend program compiles
        eng = engine(kv_page_size=8)
        shared = prompt(8)
        pairs = [np.concatenate([shared, prompt(4)]) for _ in range(4)]
        serve("paged_cold", eng, pairs[:2])
        serve("paged_repeat", eng, pairs[2:])
        eng.close()
        for name, kw in (("spec", {"speculative": "ngram", "draft_len": 3}),
                         ("quant", {"quant": "int8"}), ("tp", {"tp": 2})):
            eng = engine(**kw)
            serve(f"{name}_cold", eng, [prompt(8)])
            serve(f"{name}_repeat", eng, [prompt(10)])
            eng.close()
        return legs

    census(48)
    dense = {"prefill[b16]": 1, "slot_insert": 1, "decode_window[k1]": 1,
             "slot_reset": 1}
    assert census(32) == {
        "bucket16_first": dense,
        "bucket16_repeat": {},                   # a repeat compiles NOTHING
        "bucket32_new": {"prefill[b32]": 1},     # the new bucket's prefill
        "bucket32_repeat": {},
        "sample_cold": {},     # sampling planes are data in the one window
        "sample_repeat": {},   # program: no (temperature, top_p, seed) forks
        "paged_cold": {**dense, "extend[b16]": 1},
        "paged_repeat": {},    # paging adds programs once, not per request
        # the verify window in place of the decode window; the host-side
        # draft upload (slot_draft) compiles nothing
        "spec_cold": {"prefill[b16]": 1, "slot_insert": 1, "slot_reset": 1,
                      "verify_window[k4]": 1},
        "spec_repeat": {},
        # int8 weights and a 2-chip tp mesh change what a program holds,
        # never how many there are
        "quant_cold": dense, "quant_repeat": {},
        "tp_cold": dense, "tp_repeat": {},
    }
