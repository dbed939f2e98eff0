"""Disaggregated prefill/decode serving (ISSUE 16).

The decisive properties of the role-typed tier:

* PARITY — a prefill(2)+decode tier produces token-identical greedy
  output to one monolithic paged engine: packaging a prefill into a
  :class:`HandoffPacket`, installing it page-by-page on the decode
  replica, and picking the first token from the handed-off logits row is
  invisible in the tokens.
* ROLE SEPARATION — prefill replicas generate ZERO tokens (the pick
  runs decode-side), decode replicas run ZERO prefill programs
  (``prewarm()["by_site"]`` pins the per-role program family), and a
  decode-role engine refuses direct submissions outright.
* EXACTLY-ONCE — a ``kv-handoff`` chaos hit releases the packet's hold
  and re-dispatches through a fresh prefill; a DOUBLE failover (a
  prefill replica dies with queued work, then a decode replica dies with
  occupied slots) still retires every request ``done`` with identical
  tokens, each streamed token delivered exactly once across attempts
  (the delivered high-water mark suppresses replayed prefixes).
* ROLLUP — ``ServingStats`` records carry their engine's ``role``, the
  router rollup groups ``per_role`` sub-rollups (decode owns the
  user-visible percentiles, prefill owns work that never retires
  locally), and everything stays strict-JSON; ``cat="handoff"`` spans
  roll up into trace_report's per-request ``handoff_ms`` column.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    Router,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [9, 1], [3, 3, 3, 3]]


def _model_and_params(seed=0):
    model = get_model("causal_lm", **KW)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _factory(model, params, roles, slots=2, **kw):
    def make_engine(tid, index):
        return InferenceEngine(
            model, params, slots=slots, max_len=16, kv_page_size=4,
            scheduler=FIFOScheduler(max_len=16, buckets=(8,), max_queue=16),
            trace_tid=tid, role=roles[index], **kw)
    return make_engine


def _reference(model, params, prompts=PROMPTS, max_new=6):
    eng = InferenceEngine(model, params, slots=2, max_len=16,
                          kv_page_size=4,
                          scheduler=FIFOScheduler(max_len=16, buckets=(8,)))
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run()
    eng.close()
    return [list(r.generated) for r in reqs]


# ----------------------------------------------------------------------
# parity + role separation


def test_disagg_parity_and_role_separation():
    """prefill+decode tier == one monolithic paged engine, token for
    token; every request hands off exactly once; the per-role rollup
    shows zero tokens generated prefill-side."""
    model, params = _model_and_params()
    want = _reference(model, params)
    roles = ["prefill", "decode"]
    r = Router(_factory(model, params, roles), 2, roles=roles)
    rrs = [r.submit(p, max_new=6) for p in PROMPTS]
    r.run_until_done(max_steps=500)
    assert [list(rr.generated) for rr in rrs] == want
    assert all(rr.status == "done" for rr in rrs)
    assert r.handoffs == len(PROMPTS)
    assert r.handoff_faults == 0
    summ = r.summary()
    # strict JSON (None, never NaN) all the way down
    json.dumps(summ, allow_nan=False)
    per_role = summ["per_role"]
    assert set(per_role) == {"prefill", "decode"}
    assert per_role["prefill"]["tokens_generated"] == 0
    assert per_role["decode"]["tokens_generated"] == sum(
        len(t) for t in want)
    # per-engine records carry their role
    roles_seen = {rec["role"] for rec in summ["per_engine"]}
    assert roles_seen == {"prefill", "decode"}
    r.close()


def test_per_role_prewarm_census():
    """The per-role program family: a decode replica compiles ZERO
    prefill/extend/insert programs, a prefill replica ZERO pick/window
    programs — the disaggregation claim the compile census pins.  A
    UNIQUE model width keeps this test's compiles out of the process
    jit cache other tests warm (``by_site`` reports compile DELTAS)."""
    model = get_model("causal_lm", **{**KW, "dim": 48, "num_classes": 17})
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    roles = ["prefill", "decode"]
    r = Router(_factory(model, params, roles), 2, roles=roles)
    warm = r.prewarm()
    pre = set(warm["replicas"][0]["by_site"])
    dec = set(warm["replicas"][1]["by_site"])
    assert not any(s.startswith(("first_pick", "decode_window[",
                                 "verify_window[")) for s in pre)
    assert any(s.startswith("prefill[") for s in pre)
    assert "handoff_gather" in pre
    assert not any(s.startswith(("prefill[", "extend[", "slot_insert"))
                   for s in dec)
    assert any(s.startswith("decode_window[") for s in dec)
    assert "first_pick" in dec and "handoff_install" in dec
    r.close()


def test_role_validation_and_decode_submit_refusal():
    model, params = _model_and_params()
    # a decode-role engine takes no direct submissions
    eng = InferenceEngine(
        model, params, slots=2, max_len=16, kv_page_size=4,
        scheduler=FIFOScheduler(max_len=16, buckets=(8,)), role="decode")
    with pytest.raises(RuntimeError, match="decode-role"):
        eng.submit([1, 2], max_new=4)
    eng.close()
    # disaggregated roles require the paged cache
    with pytest.raises(ValueError, match="kv_page_size"):
        InferenceEngine(model, params, slots=2, max_len=16,
                        scheduler=FIFOScheduler(max_len=16, buckets=(8,)),
                        role="prefill")
    # a tier needs both prefill and decode capacity
    roles = ["decode", "decode"]
    with pytest.raises(ValueError, match="prefill"):
        Router(_factory(model, params, roles), 2, roles=roles)
    # roles list must match the replica count
    with pytest.raises(ValueError, match="roles"):
        Router(_factory(model, params, ["prefill", "decode"]), 2,
               roles=["prefill"])


# ----------------------------------------------------------------------
# chaos + double failover, exactly-once


def test_kv_handoff_chaos_releases_and_redispatches_exactly_once(
        pools_refcount_zero):
    """A ``kv-handoff`` chaos hit drops the packet in flight: the router
    releases the hold, re-dispatches through a fresh prefill, and the
    wave still finishes token-identical with exactly-once streams."""
    model, params = _model_and_params()
    want = _reference(model, params)
    inj = FaultInjector(FaultPlan(seed=1, faults=(
        FaultSpec(site="kv-handoff", at=(0,)),)))
    streams: dict[int, list[int]] = {}
    roles = ["prefill", "decode"]
    r = Router(_factory(model, params, roles), 2, roles=roles, chaos=inj)
    rrs = [r.submit(p, max_new=6,
                    callback=lambda rr, tok: streams.setdefault(
                        rr.id, []).append(int(tok)))
           for p in PROMPTS]
    r.run_until_done(max_steps=500)
    assert [list(rr.generated) for rr in rrs] == want
    assert all(rr.status == "done" for rr in rrs)
    assert r.handoff_faults == 1
    assert sum(rr.redispatches for rr in rrs) == 1
    for rr in rrs:
        assert streams.get(rr.id, []) == list(rr.generated)
    assert pools_refcount_zero(r)     # the dropped packet's hold was released
    r.close()


def test_double_failover_prefill_then_decode_exactly_once():
    """A prefill replica dies with queued admissions, then a decode
    replica dies with occupied slots: both casualties re-dispatch (full
    re-prefill, fresh handoff), every request retires ``done`` with
    identical tokens, and the delivered high-water mark keeps each
    stream exactly-once across all attempts."""
    model, params = _model_and_params()
    want = _reference(model, params)
    roles = ["prefill", "prefill", "decode", "decode"]
    streams: dict[int, list[int]] = {}
    r = Router(_factory(model, params, roles), 4, roles=roles)
    rrs = [r.submit(p, max_new=6,
                    callback=lambda rr, tok: streams.setdefault(
                        rr.id, []).append(int(tok)))
           for p in PROMPTS]
    # kill a prefill replica while its queue holds admissions
    dead_p = next(rep for rep in r.replicas
                  if rep.role == "prefill" and len(rep.engine.scheduler))
    r._fail_replica(dead_p, RuntimeError("induced prefill kill"))
    r.step()
    # now kill a decode replica holding live decodes
    dead_d = next(rep for rep in r.replicas
                  if rep.role == "decode" and rep.alive
                  and rep.engine.occupied)
    r._fail_replica(dead_d, RuntimeError("induced decode kill"))
    r.run_until_done(max_steps=500)
    assert [list(rr.generated) for rr in rrs] == want
    assert all(rr.status == "done" for rr in rrs)
    assert r.failovers == 2
    assert sum(rr.redispatches for rr in rrs) >= 2
    for rr in rrs:
        assert streams.get(rr.id, []) == list(rr.generated)
    summ = r.summary()
    assert summ["replicas_failed"] == 2 and summ["failovers"] == 2
    assert summ["n_engine_fault"] >= 2
    json.dumps(summ, allow_nan=False)
    r.close()


# ----------------------------------------------------------------------
# the headline, in router steps: short requests do not wait behind a
# long-prompt stream that saturates the prefill replica


LONG_LEN, LONG_NEW = 12, 5     # a bucket-16 prompt that holds a decode slot
SHORT_LEN, SHORT_NEW = 3, 2    # a bucket-8 prompt, two tokens
N_LONGS, N_SHORTS = 8, 3


def _drip_tier(model, params, roles, slots, **kw):
    def make_engine(tid, index):
        return InferenceEngine(
            model, params, slots=slots[index], max_len=32, kv_page_size=4,
            kv_pages=96,
            scheduler=FIFOScheduler(max_len=32, buckets=(8, 16),
                                    max_queue=64),
            trace_tid=tid,
            role=(roles[index] if roles is not None else "both"),
            **({k: v[index] for k, v in kw.items()}))
    return Router(make_engine, len(slots), roles=roles)


def _drip(router, longs, shorts):
    """Arrivals pinned to router steps — long k at step k (one a step: the
    saturating stream), short j at step 1 + 3j, longs first within a step
    so shorts really queue behind them.  Greedy, fixed seeds: the step of
    a request's first token is a property of the queueing structure and
    repeats exactly.  Returns one record per request, in arrival order."""
    arrivals = sorted(
        [(k, 0, p, LONG_NEW) for k, p in enumerate(longs)]
        + [(1 + 3 * j, 1, p, SHORT_NEW) for j, p in enumerate(shorts)],
        key=lambda a: a[:2])
    cur, recs, i = [0], [], 0
    while i < len(arrivals) or router.outstanding:
        while i < len(arrivals) and arrivals[i][0] <= cur[0]:
            _, short, prompt, max_new = arrivals[i]
            i += 1
            rec = {"short": bool(short), "submit": cur[0], "first": None,
                   "stream": []}

            def _cb(rr, tok, rec=rec):
                rec["stream"].append(int(tok))
                if rec["first"] is None:
                    rec["first"] = cur[0]

            rec["rr"] = router.submit(prompt, max_new, callback=_cb)
            recs.append(rec)
        router.step()
        cur[0] += 1
        assert cur[0] < 3000, f"{router.outstanding} outstanding"
    return recs


def _short_ttft_steps(recs):
    return [r["first"] - r["submit"] + 1 for r in recs if r["short"]]


def _drip_prompts():
    rng = np.random.default_rng(7)
    longs = [rng.integers(1, 16, size=(LONG_LEN,)).astype(np.int32)
             for _ in range(N_LONGS)]
    shorts = [rng.integers(1, 16, size=(SHORT_LEN,)).astype(np.int32)
              for _ in range(N_SHORTS)]
    return longs, shorts


def test_short_ttft_in_steps_flat_under_a_saturating_long_stream(
        pools_refcount_zero):
    """prefill(2) + decode(8) slots.  The short drip alone is the control;
    the same drip beside one long prompt a step may wait no more than 1.15
    times as many router steps for its first token.  Every request hands
    off exactly once, the mixed stream's tokens equal an equal-slot
    monolithic tier's, and after prewarm and first traffic neither tier
    compiles anything."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        CompileTracker,
    )

    model, params = _model_and_params()
    longs, shorts = _drip_prompts()
    tracker = CompileTracker.install()
    roles = ["prefill", "decode"]
    r = _drip_tier(model, params, roles, [2, 8])
    r.prewarm()
    # host-glue programs outside any site compile with the first request
    # through a fresh process; the census pins the steady state
    _drip(r, longs[:1], shorts[:1])
    before = tracker.snapshot()
    control = _drip(r, [], shorts)
    handoffs0 = r.handoffs
    loaded = _drip(r, longs, shorts)
    assert CompileTracker.delta(
        tracker.snapshot(), before)["n_compiled_programs"] == 0
    assert max(_short_ttft_steps(loaded)) <= 1.15 * max(
        _short_ttft_steps(control))
    assert all(rec["rr"].status == "done" for rec in control + loaded)
    assert r.handoffs - handoffs0 == len(loaded)
    assert all(rec["stream"] == list(rec["rr"].generated) for rec in loaded)
    assert pools_refcount_zero(r)
    r.close()

    mono = _drip_tier(model, params, None, [5, 5])
    mono.prewarm()
    before = tracker.snapshot()
    mono_recs = _drip(mono, longs, shorts)
    assert CompileTracker.delta(
        tracker.snapshot(), before)["n_compiled_programs"] == 0
    mono.close()
    tokens = [list(rec["rr"].generated) for rec in loaded]
    assert all(tokens)
    assert tokens == [list(rec["rr"].generated) for rec in mono_recs]


def test_handoff_reshards_between_disjoint_tp_groups(eight_devices,
                                                     pools_refcount_zero):
    """prefill tp=2 -> decode tp=2 on DISJOINT 2-chip groups: every page
    that crosses is assembled host-side from one mesh's shards and laid
    out again on the other's.  Tokens equal the tp=1 monolithic tier's."""
    from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
        tp_device_groups,
    )

    model, params = _model_and_params()
    longs, shorts = _drip_prompts()
    mono = _drip_tier(model, params, None, [5, 5])
    want = [list(rec["rr"].generated)
            for rec in _drip(mono, longs, shorts)]
    mono.close()

    groups = tp_device_groups(2, 2)
    r = _drip_tier(model, params, ["prefill", "decode"], [2, 8],
                   tp=[2, 2], tp_devices=groups)
    recs = _drip(r, longs, shorts)
    dev_ids = [{d.id for d in rep.engine._mesh.devices.flatten()}
               for rep in r.replicas]
    assert len(dev_ids[0]) == len(dev_ids[1]) == 2
    assert not dev_ids[0] & dev_ids[1]
    assert all(rec["rr"].status == "done" for rec in recs)
    assert r.handoffs == len(recs)
    assert all(want) and [list(rec["rr"].generated) for rec in recs] == want
    assert pools_refcount_zero(r)
    r.close()


# ----------------------------------------------------------------------
# tracing rollup


def test_handoff_trace_rollup(tmp_path):
    """Handoff gather/install land ``cat="handoff"`` spans; the exported
    trace validates and trace_report rolls them up into per-request
    ``handoff_ms`` with page counts."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        Tracer,
        validate_trace,
    )

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import trace_report

    model, params = _model_and_params()
    tracer = Tracer()
    roles = ["prefill", "decode"]
    r = Router(_factory(model, params, roles, tracer=tracer), 2,
               roles=roles, tracer=tracer)
    rrs = [r.submit(p, max_new=4) for p in PROMPTS[:3]]
    r.run_until_done(max_steps=500)
    assert all(rr.status == "done" for rr in rrs)
    r.close()
    path = tmp_path / "trace.json"
    tracer.export_trace(str(path))
    assert validate_trace(str(path)) == []

    report = trace_report.analyze(json.loads(path.read_text()))
    names = {row["phase"] for row in report["phases"]}
    assert {"handoff/gather", "handoff/install"} <= names
    rolled = [row for row in report["requests"] if "handoff" in row]
    assert rolled, "no request rolled up handoff spans"
    assert any(row["handoff"]["pages"] > 0 for row in rolled)
    for row in rolled:
        assert row["handoff_ms"] >= 0.0
        assert row["handoff"]["dedup_pages"] <= row["handoff"]["pages"]


def test_front_door_on_its_own_tracer_joins_the_tier_by_merge(
        tmp_path, pools_refcount_zero):
    """Two processes in miniature: the front door traces into one tracer,
    the prefill/decode tier into another.  Each export alone is an island
    (the tier's tree has no ``http_request``); ``merge_traces`` joins them
    through the hex ``span_ctx`` / ``parent_ctx`` edge into one connected
    tree a stream, the handoff's gather and install inside it."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        FrontDoor,
        FrontDoorClient,
        ServingDaemon,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        TraceContext,
        Tracer,
        merge_traces,
        trace_forest,
        validate_trace,
    )

    model, params = _model_and_params()
    front_tr, tier_tr = Tracer(), Tracer()
    roles = ["prefill", "decode"]
    router = Router(_factory(model, params, roles, tracer=tier_tr), 2,
                    roles=roles, tracer=tier_tr)
    daemon = ServingDaemon(router, max_queue=16).start()
    fd = FrontDoor(daemon, tracer=front_tr).start_in_thread()
    try:
        cli = FrontDoorClient("127.0.0.1", fd.port, timeout=120.0)
        tids = []
        for prompt in PROMPTS[:3]:
            assert len(list(cli.stream(prompt, 4, deadline_s=120.0))) == 4
            assert cli.last_terminal["status"] == "done"
            tids.append(TraceContext.parse_traceparent(
                cli.last_headers["traceparent"]).trace_id)
        assert router.handoffs >= len(tids)
    finally:
        fd.stop()
        drained = daemon.drain(timeout=30.0)
        pools = pools_refcount_zero(router)
        daemon.close()
    assert drained and pools
    assert front_tr.open_spans == 0 and tier_tr.open_spans == 0
    path = str(tmp_path / "merged.json")
    doc = merge_traces([front_tr, tier_tr], path, names=["frontdoor", "tier"])
    assert validate_trace(path) == []
    forest, islands = trace_forest(doc), trace_forest(tier_tr.to_doc())
    for tid in tids:
        assert "http_request" not in islands[tid]["names"]
        assert forest[tid]["connected"], forest[tid]
        assert {"http_request", "daemon_request", "request", "gather",
                "install"} <= set(forest[tid]["names"])
