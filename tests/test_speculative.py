"""Speculative decoding (ISSUE 9): verify window, drafter, engine, tier.

The decisive properties:

* EXACTNESS — the verify window emits exactly the target model's greedy
  argmax chain no matter what the drafter proposed: spec-vs-plain parity
  holds token-for-token across decode_ahead, dense/paged layouts, and
  int8-quantized KV, for good drafts, garbage drafts, and empty drafts.
* LIFECYCLE — retirement mid-acceptance (EOS inside an accepted block,
  budget shorter than the block, lapsed deadline) delivers exactly the
  tokens plain decode would; the KV cursor rewind means rejected lanes
  are overwritten, never served.
* CONTRACT — the chaos ``serving-step`` site still counts one event per
  WINDOW dispatch, identical across layouts for the same mode; router
  failover replays a partially-accepted request exactly-once.
* LAUNCH — ``prewarm()`` compiles the engine's whole program family
  before the first request (zero compiles during serving afterwards),
  without consuming the rng stream or corrupting idle state;
  ``Router.prewarm()`` fans it across replicas.
* ROLLUP — ``ServingStats`` acceptance counters sum through ``merge``
  with ratios recomputed over merged totals (None, never NaN), and the
  per-request trace rollup carries draft/verify/accept spans.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.generate import (
    make_decode_step,
    make_prefill,
    make_verify_window,
)
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    NgramDrafter,
    Router,
    ServingStats,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)

PROMPTS = [[7, 3, 11, 2, 5], [4, 9], [1, 2, 3, 1, 2, 3, 1], [6],
           [5, 5, 5, 5], [2, 8, 2, 8, 2, 8]]


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("buckets", (8,))
    return InferenceEngine(model, params, **kw)


def _serve(model, params, max_new=10, **kw):
    eng = _engine(model, params, **kw)
    reqs = [eng.submit(np.asarray(p, np.int32), max_new=max_new)
            for p in PROMPTS]
    eng.run()
    out = [list(r.generated) for r in reqs]
    eng.close()
    return out


# ----------------------------------------------------------------------
# the verify-window primitive (core/generate.py)


def test_verify_window_matches_stepwise_any_draft():
    """The verify window's emitted tokens are exactly the sequential
    greedy chain for ORACLE drafts (max acceptance), GARBAGE drafts (zero
    acceptance), and EMPTY drafts (plain decode step) — exactness cannot
    depend on draft quality, only throughput can."""
    model, params = _model_and_params(seed=1)
    prompts = [np.asarray([7, 3, 11, 2, 5], np.int32),
               np.asarray([4, 9], np.int32)]
    bucket, max_len, draft_len = 8, 64, 3
    k = draft_len + 1
    batch = np.zeros((2, bucket), np.int32)
    lens = np.asarray([p.size for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        batch[i, : p.size] = p

    prefill = make_prefill(model, max_len)
    step = make_decode_step(model, max_len, ragged=True)
    cache, last = prefill(params, jnp.asarray(batch), jnp.asarray(lens))
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    ref = [np.asarray(tok)]
    for _ in range(23):
        cache, logits = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ref.append(np.asarray(tok))
    ref = np.stack(ref, axis=1)  # (2, 24)

    verify = make_verify_window(model, max_len, draft_len)
    rng = np.random.RandomState(0)

    def run_spec(draft_fn, n_target=24):
        cache, last = prefill(params, jnp.asarray(batch), jnp.asarray(lens))
        pending = np.asarray(jnp.argmax(last, axis=-1)).astype(np.int32)
        out = [[int(pending[0])], [int(pending[1])]]
        accs = []
        while min(len(o) for o in out) < n_target:
            chunk = np.zeros((2, k), np.int32)
            dls = np.zeros((2,), np.int32)
            chunk[:, 0] = pending
            for b in range(2):
                d = np.asarray(draft_fn(b, out[b]), np.int32)[:draft_len]
                chunk[b, 1:1 + d.size] = d
                dls[b] = d.size
            cache2, toks, acc, last2 = verify(
                params, cache, jnp.asarray(chunk), jnp.asarray(dls))
            cache = cache2
            toks, acc = np.asarray(toks), np.asarray(acc)
            accs.append(acc.copy())
            for b in range(2):
                n_emit = int(acc[b]) + 1
                out[b].extend(int(t) for t in toks[b, :n_emit])
                pending[b] = toks[b, n_emit - 1]
                # `last` mirrors the final emitted token per row
                assert int(np.asarray(last2)[b]) == int(toks[b, n_emit - 1])
        return out, accs

    def oracle(b, hist):
        return ref[b, len(hist): len(hist) + draft_len]

    def garbage(b, hist):
        return rng.randint(0, 16, size=draft_len)

    def empty(b, hist):
        return np.zeros((0,), np.int32)

    for name, fn in (("oracle", oracle), ("garbage", garbage),
                     ("empty", empty)):
        out, accs = run_spec(fn)
        for b in range(2):
            assert out[b][:24] == list(ref[b]), name
        if name == "oracle":      # oracle accepts every lane
            assert all(int(a) == draft_len for row in accs for a in row)
        if name == "empty":       # empty drafts emit exactly one token
            assert all(int(a) == 0 for row in accs for a in row)


def test_verify_window_validation():
    model, _ = _model_and_params()
    with pytest.raises(ValueError, match="draft_len"):
        make_verify_window(model, 32, 0)
    with pytest.raises(ValueError, match="max_len"):
        make_verify_window(model, 0, 3)
    verify = make_verify_window(model, 32, 3)
    _, params = _model_and_params()
    with pytest.raises(ValueError, match="chunk"):
        # chunk must be (B, draft_len + 1)
        prefill = make_prefill(model, 32)
        cache, _ = prefill(params, jnp.ones((1, 8), jnp.int32),
                           jnp.asarray([3], jnp.int32))
        verify(params, cache, jnp.ones((1, 3), jnp.int32),
               jnp.ones((1,), jnp.int32))


# ----------------------------------------------------------------------
# the drafter (serving/drafter.py)


def test_drafter_periodic_extension_and_lookup():
    d = NgramDrafter(draft_len=6)
    # period-3 stream: the suffix 3-gram matched 3 back extends
    # periodically to the full draft length
    ctx = np.asarray([4, 7, 9, 4, 7, 9, 4, 7, 9], np.int32)
    np.testing.assert_array_equal(d.draft(ctx),
                                  [4, 7, 9, 4, 7, 9])
    # a non-adjacent match: continuation copied from after the match
    ctx = np.asarray([1, 2, 3, 4, 5, 6, 7, 1, 2, 3], np.int32)
    np.testing.assert_array_equal(d.draft(ctx),
                                  [4, 5, 6, 7, 1, 2])
    # no repetition anywhere -> empty draft
    assert d.draft(np.asarray([1, 2, 3, 4, 5], np.int32)).size == 0
    # too-short context -> empty draft (no earlier occurrence possible)
    assert d.draft(np.asarray([3], np.int32)).size == 0
    # max_context bounds the scan: a match outside the suffix is invisible
    tight = NgramDrafter(draft_len=4, max_context=4)
    assert tight.draft(np.asarray([8, 9, 1, 2, 3, 4, 5], np.int32)).size == 0


def test_drafter_validation():
    with pytest.raises(ValueError, match="draft_len"):
        NgramDrafter(0)
    with pytest.raises(ValueError, match="ngram"):
        NgramDrafter(3, max_ngram=2, min_ngram=3)
    with pytest.raises(ValueError, match="max_context"):
        NgramDrafter(3, max_context=-1)


# ----------------------------------------------------------------------
# engine parity (the tentpole's exactness gate)


@pytest.mark.parametrize("layout", ["dense", "paged", "int8", "paged_int8"])
def test_engine_spec_parity_across_decode_ahead_and_layouts(layout):
    """Speculative output is token-identical to plain greedy decode for
    every decode_ahead in {1, 4, 8}, on the dense, paged, int8-KV, and
    paged-int8 layouts — the exactness gate behind every reported
    speedup."""
    over = {"kv_cache_dtype": "int8"} if "int8" in layout else {}
    model, params = _model_and_params(**over)
    paged = dict(kv_page_size=8, kv_pages=16) if "paged" in layout else {}
    spec = _serve(model, params, speculative="ngram", draft_len=3, **paged)
    for k in (1, 4, 8):
        plain = _serve(model, params, decode_ahead=k, **paged)
        assert plain == spec, (layout, k)


def test_engine_spec_draft_len_sweep():
    """Parity holds for every draft length (window shape k = draft_len+1
    changes; the emitted chain must not)."""
    model, params = _model_and_params(seed=3)
    plain = _serve(model, params)
    for dl in (1, 2, 5):
        assert _serve(model, params, speculative="ngram",
                      draft_len=dl) == plain, dl


def test_engine_spec_tight_cache_overrun():
    """max_len exactly prompt_bucket + max_new: verify chunks overrun the
    cursor clamp on the last window and the per-position clamped write
    must not corrupt earlier (live) positions — parity pins it."""
    model, params = _model_and_params(seed=5)
    kw = dict(max_len=8 + 10, buckets=(8,))
    spec = _serve(model, params, speculative="ngram", draft_len=3, **kw)
    assert _serve(model, params, decode_ahead=4, **kw) == spec


def test_speculative_validation():
    model, params = _model_and_params()
    with pytest.raises(ValueError, match="speculative"):
        _engine(model, params, speculative="tree")
    with pytest.raises(ValueError, match="draft_len"):
        _engine(model, params, speculative="ngram", draft_len=0)
    # ISSUE 13 lifted the old spec+sampling refusal: the verify window
    # accepts drafts by rejection sampling, so this must now construct
    eng = _engine(model, params, speculative="ngram", temperature=0.7,
                  rng=jax.random.PRNGKey(0))
    eng.close()
    wmodel, wparams = _model_and_params(window=8)
    with pytest.raises(ValueError, match="sliding-window"):
        _engine(wmodel, wparams, speculative="ngram")


# ----------------------------------------------------------------------
# retirement mid-acceptance


def test_retirement_mid_acceptance_eos_budget_deadline():
    """A window's accepted block can cross a request's stop condition:
    EOS inside the block stops AT the EOS, budget truncates the block,
    and a lapsed deadline cancels before the window — each delivering
    exactly what plain decode delivers."""
    model, params = _model_and_params(seed=7)
    base = _serve(model, params, max_new=12)
    # EOS = the first token of request 0's plain run that has not occurred
    # earlier, is not a decode_ahead=4 window boundary (token 0 is the
    # prefill's; windows cover 1..4, 5..8, ...), and stays out of the
    # budget request's 5 tokens — so spec must stop request 0 AT it,
    # mid-block, whatever stream this installation's random init greedy-
    # decodes to, and the budget request retires on its budget
    stream = [int(t) for t in base[0]]
    stop_at = next(
        (i for i, t in enumerate(stream)
         if i >= 1 and i % 4 and t not in stream[:i]
         and t not in list(base[4][:5])), None)
    if stop_at is None:
        pytest.skip(f"greedy stream {stream} has no fresh token mid-window")
    eos = stream[stop_at]

    def run(**kw):
        clock = _FakeClock()
        eng = _engine(model, params, eos_id=eos, clock=clock, **kw)
        rs = [eng.submit(np.asarray(p, np.int32), max_new=12)
              for p in PROMPTS[:3]]
        # deadline already lapsed when the loop first looks: cancelled
        late = eng.submit(np.asarray(PROMPTS[3], np.int32), max_new=12,
                          deadline_s=0.5)
        # budget 5: retires mid-block when acceptance crosses it
        tiny = eng.submit(np.asarray(PROMPTS[4], np.int32), max_new=5)
        clock.t += 5.0
        eng.run()
        eng.close()
        return rs, late, tiny

    prs, plate, ptiny = run(decode_ahead=4)
    srs, slate, stiny = run(speculative="ngram", draft_len=3)
    for p, s in zip(prs, srs):
        assert list(s.generated) == list(p.generated)
        assert s.status == p.status == "done"
    # the EOS request stopped at the EOS (not at the window boundary)
    assert srs[0].generated[-1] == eos
    assert len(srs[0].generated) == stop_at + 1
    assert slate.status == plate.status == "cancelled"
    assert slate.generated == []
    assert list(stiny.generated) == list(ptiny.generated)
    assert len(stiny.generated) == 5 and stiny.status == "done"


# ----------------------------------------------------------------------
# chaos contract


def test_chaos_serving_step_layout_and_speculation_invariant():
    """One serving-step event per WINDOW dispatch, in spec mode too; the
    count is layout-invariant (dense == paged at equal acceptance — the
    outputs are identical, so the window trajectory is too), and a
    transient fault mid-stream is absorbed with exact output parity."""
    model, params = _model_and_params(seed=11)
    prompt = np.asarray([5, 3, 1, 5, 3, 1, 5], np.int32)

    def windows(**kw):
        eng = _engine(model, params, **kw)
        r = eng.submit(prompt, max_new=11)
        eng.run()
        n = eng.stats.summary()["n_windows"]
        eng.close()
        return n, list(r.generated)

    spec = dict(speculative="ngram", draft_len=3)
    n_dense, out_dense = windows(**spec)
    n_paged, out_paged = windows(kv_page_size=8, kv_pages=12, **spec)
    assert out_dense == out_paged
    assert n_dense == n_paged  # layout-invariant window trajectory

    inj = FaultInjector(FaultPlan(seed=0, faults=(
        FaultSpec(site="serving-step", at=(1,)),)))
    eng = _engine(model, params, chaos=inj, stall_timeout_s=60.0, **spec)
    r = eng.submit(prompt, max_new=11)
    eng.run()
    eng.close()
    assert r.status == "done" and list(r.generated) == out_dense
    # one event per dispatch ATTEMPT: clean windows + the faulted one
    assert inj.events("serving-step") == n_dense + 1
    assert inj.summary()["faults_injected"] == 1


# ----------------------------------------------------------------------
# router failover replay


def test_router_failover_replays_partial_acceptance_exactly_once():
    """Chaos kills a spec replica mid-wave — after some requests already
    delivered partially-accepted blocks.  Failover re-dispatches the
    collateral; every stream delivers each token exactly once (the
    delivered high-water suppresses the replayed accepted prefix) and
    final outputs are token-identical to a fault-free engine."""
    model, params = _model_and_params()

    def factory(**ekw):
        def make_engine(tid):
            return InferenceEngine(
                model, params, slots=2, max_len=48,
                scheduler=FIFOScheduler(max_len=48, buckets=(8,),
                                        max_queue=16),
                speculative="ngram", draft_len=3, trace_tid=tid, **ekw)
        return make_engine

    want = _serve(model, params, max_new=8, speculative="ngram",
                  draft_len=3)
    # fire at window 1: window 0 already delivered each slot's first
    # accepted block, so the replayed request is partially delivered
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-step", kind="transient", at=(1,)),)))
    streams: dict[int, list[int]] = {}
    r = Router(factory(chaos=inj, stall_timeout_s=None), 2)
    rrs = [r.submit(np.asarray(p, np.int32), max_new=8,
                    callback=lambda rr, tok: streams.setdefault(
                        rr.id, []).append(int(tok)))
           for p in PROMPTS]
    r.run_until_done()
    assert [list(rr.generated) for rr in rrs] == want
    assert all(rr.status == "done" for rr in rrs)
    assert r.failovers == 1
    moved = [rr for rr in rrs if rr.redispatches]
    assert moved  # the fault really displaced someone
    # exactly-once across the replay: streams == final outputs, no
    # duplicated accepted prefix
    for rr in rrs:
        assert streams.get(rr.id, []) == list(rr.generated)
    summ = r.summary()
    assert summ["accept_rate"] is not None  # rollup carries acceptance
    assert summ["drafted_tokens"] > 0
    r.close()


# ----------------------------------------------------------------------
# prewarm (ROADMAP 5a)


def test_engine_prewarm_compiles_everything_before_traffic():
    """After prewarm, a full serve (admission, windows, retirement)
    compiles ZERO new programs, and output equals a cold engine's."""
    model, params = _model_and_params(seed=2)
    cold = _serve(model, params, speculative="ngram", draft_len=3)
    for kw in (dict(speculative="ngram", draft_len=3),
               dict(speculative="ngram", draft_len=3,
                    kv_page_size=8, kv_pages=16),
               dict(decode_ahead=4)):
        eng = _engine(model, params, **kw)
        rep = eng.prewarm()
        assert rep["programs"] > 0 and rep["wall_s"] >= 0
        before = eng._compile.snapshot()
        reqs = [eng.submit(np.asarray(p, np.int32), max_new=10)
                for p in PROMPTS]
        eng.run()
        d = CompileTracker.delta(eng._compile.snapshot(), before)
        assert d["n_compiled_programs"] == 0, (kw, d)
        if "speculative" in kw:
            assert [list(r.generated) for r in reqs] == cold
        eng.close()


def test_prewarm_refuses_busy_or_closed_engine():
    model, params = _model_and_params()
    eng = _engine(model, params)
    eng.submit(np.asarray([1, 2], np.int32), max_new=4)
    with pytest.raises(RuntimeError, match="busy"):
        eng.prewarm()
    eng.run()
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.prewarm()


def test_router_prewarm_fans_out():
    model, params = _model_and_params()

    def make_engine(tid):
        return InferenceEngine(
            model, params, slots=2, max_len=48,
            scheduler=FIFOScheduler(max_len=48, buckets=(8,), max_queue=16),
            trace_tid=tid)

    with Router(make_engine, 2) as r:
        rep = r.prewarm()
        assert sorted(rep["replicas"]) == [0, 1]
        assert all(v["programs"] > 0 for v in rep["replicas"].values())
        assert rep["total_s"] >= 0
        rrs = [r.submit(np.asarray(p, np.int32), max_new=6)
               for p in PROMPTS[:3]]
        r.run_until_done()
        assert all(rr.status == "done" for rr in rrs)


# ----------------------------------------------------------------------
# stats rollup


def test_stats_spec_counters_summary_merge_strict_json():
    """spec() counters sum; accept_rate/useful_tokens_per_window are None
    (not NaN) with no traffic; merge re-derives ratios over MERGED totals
    and the whole record survives a strict JSON round trip."""
    empty = ServingStats(slots=2, decode_ahead=1).summary()
    assert empty["drafted_tokens"] == 0
    assert empty["accept_rate"] is None
    assert empty["useful_tokens_per_window"] is None
    json.loads(json.dumps(empty, allow_nan=False))

    a = ServingStats(slots=2, decode_ahead=1)
    a.spec(3, 2)
    a.spec(3, 1)
    a.window(0.001, 0.0005, steps=8, waste=3)
    sa = a.summary()
    assert sa["drafted_tokens"] == 6 and sa["accepted_tokens"] == 3
    assert sa["corrected_tokens"] == 2
    assert sa["accept_rate"] == 0.5
    assert sa["useful_tokens_per_window"] == 5.0

    b = ServingStats(slots=2, decode_ahead=1)
    b.spec(2, 2)
    b.window(0.001, 0.0005, steps=4, waste=0)
    merged = ServingStats.merge([a, b])
    assert merged["drafted_tokens"] == 8
    assert merged["accepted_tokens"] == 5
    # recomputed over merged totals (5/8), NOT averaged per-engine rates
    assert merged["accept_rate"] == 0.625
    assert merged["useful_tokens_per_window"] == 4.5
    json.loads(json.dumps(merged, allow_nan=False))
    # spec-less engines merge to None, never NaN
    idle = ServingStats.merge([ServingStats(slots=1, decode_ahead=1)])
    assert idle["accept_rate"] is None
    json.loads(json.dumps(idle, allow_nan=False))


def test_engine_stats_accept_rate_live():
    model, params = _model_and_params(seed=4)
    eng = _engine(model, params, speculative="ngram", draft_len=3)
    for p in PROMPTS[:3]:
        eng.submit(np.asarray(p, np.int32), max_new=10)
    eng.run()
    s = eng.stats.summary()
    eng.close()
    assert s["drafted_tokens"] > 0
    assert 0.0 <= s["accept_rate"] <= 1.0
    assert s["corrected_tokens"] > 0  # one free token per slot-window
    assert s["useful_tokens_per_window"] is not None
    json.loads(json.dumps(s, allow_nan=False))


# ----------------------------------------------------------------------
# tracing rollup


def test_trace_spans_and_report_rollup(tmp_path):
    """Spec windows land draft/verify/accept spans on each request's
    track; the exported trace validates and scripts/trace_report.py rolls
    them up per request with an accept_rate column."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        Tracer,
        validate_trace,
    )

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import trace_report

    model, params = _model_and_params(seed=6)
    tracer = Tracer()
    eng = _engine(model, params, speculative="ngram", draft_len=3,
                  tracer=tracer)
    for p in PROMPTS[:3]:
        eng.submit(np.asarray(p, np.int32), max_new=8)
    eng.run()
    eng.close()
    path = tmp_path / "trace.json"
    tracer.export_trace(str(path))
    assert validate_trace(str(path)) == []

    report = trace_report.analyze(json.loads(path.read_text()))
    names = {row["phase"] for row in report["phases"]}
    assert {"speculative/draft", "speculative/verify",
            "speculative/accept"} <= names
    reqs = report["requests"]
    assert len(reqs) == 3
    for row in reqs:
        assert "speculative" in row
        assert row["speculative"]["windows"] > 0
        assert row["speculative"]["drafted"] >= row["speculative"]["accepted"]
        assert row["accept_rate"] is None or 0.0 <= row["accept_rate"] <= 1.0
    json.dumps(report, allow_nan=False)
