"""Decode-ahead windows, prompt prefix cache, and prefill overlap (ISSUE 5).

The decisive properties:

* WINDOW PARITY — ``make_decode_window`` (a lax.scan of k fused
  decode+pick steps) emits exactly the tokens k sequential
  ``make_decode_step`` calls emit, and the engine's greedy output is
  token-for-token identical to ``make_generator`` for EVERY
  ``decode_ahead`` — the speedup is bought with fewer host syncs, never
  with different tokens.
* BOUNDED WASTE — EOS/budget/deadline retirement mid-window discards the
  ≤k−1 overrun tokens (never delivered, never counted) and the KV cursor
  clamps at ``max_len`` so overrun writes stay inside the row.
* PREFIX CACHE — a hit reuses the stored prefill row + last-position
  logits (prefill dispatch skipped, output identical — every admission
  re-picks its own first token, so the cache is sampling-safe; ISSUE 13
  lifted the old greedy-only construction guard); the LRU is
  byte-bounded.
* CONTRACT — the chaos ``serving-step`` site counts WINDOWS (one event
  per dispatch, stable across k) and the engine/scheduler bucket sets
  cannot silently drift apart.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.generate import (
    make_decode_step,
    make_decode_window,
    make_generator,
    make_prefill,
)
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    PrefixCache,
    ServingStats,
)

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return model, params


class _FakeClock:
    """Deterministic injectable clock for deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("buckets", (8,))
    return InferenceEngine(model, params, **kw)


# ----------------------------------------------------------------------
# the window primitive (core/generate.py)


def test_decode_window_matches_stepwise():
    """One make_decode_window call == k sequential make_decode_step calls:
    same cache evolution, same tokens, and `last` is the final column."""
    model, params = _model_and_params(seed=1)
    prompts = [np.asarray([7, 3, 11, 2, 5], np.int32),
               np.asarray([4, 9], np.int32)]
    bucket, max_len, k = 8, 32, 5
    batch = np.zeros((2, bucket), np.int32)
    lens = np.asarray([p.size for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        batch[i, : p.size] = p

    prefill = make_prefill(model, max_len)
    cache0, last = prefill(params, jnp.asarray(batch), jnp.asarray(lens))
    tok0 = jnp.argmax(last, axis=-1).astype(jnp.int32)

    step = make_decode_step(model, max_len, ragged=True)
    cache, tok = cache0, tok0
    want = []
    for _ in range(k):
        cache, logits = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want.append(np.asarray(tok))
    want = np.stack(want, axis=1)  # (2, k)

    win = make_decode_window(model, max_len, window=k)
    # re-prefill: the stepwise loop above consumed cache0's buffers
    cache0, last = prefill(params, jnp.asarray(batch), jnp.asarray(lens))
    _, blk, last_tok = win(params, cache0, tok0)
    np.testing.assert_array_equal(np.asarray(blk), want)
    np.testing.assert_array_equal(np.asarray(last_tok), want[:, -1])


def test_decode_window_active_mask_and_validation():
    """Inactive rows emit pad_id for the whole window (their cache rows
    still advance in lockstep — wasted FLOPs, never corruption), and the
    constructor rejects a nonsensical window."""
    model, params = _model_and_params(seed=2)
    max_len, k, pad = 24, 3, 0
    prefill = make_prefill(model, max_len)
    prompt = jnp.asarray([[5, 6, 7, 8], [1, 2, 3, 4]], jnp.int32)
    cache, last = prefill(params, prompt)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    win = make_decode_window(model, max_len, window=k, pad_id=pad)
    _, blk, _ = win(params, cache, tok, active=jnp.asarray([True, False]))
    blk = np.asarray(blk)
    assert (blk[1] == pad).all()          # masked row: pad all the way
    assert (blk[0] != pad).any() or True  # live row decoded normally
    with pytest.raises(ValueError, match="window"):
        make_decode_window(model, max_len, window=0)
    with pytest.raises(ValueError, match="temperature"):
        make_decode_window(model, max_len, window=2, top_k=3)


# ----------------------------------------------------------------------
# engine parity across k


def test_engine_parity_across_decode_ahead():
    """Greedy engine output is token-identical to the one-shot generator
    for every decode_ahead — including k that does NOT divide any budget
    and k larger than the shortest budget — while the window count drops
    ~k-fold."""
    model, params = _model_and_params(seed=3)
    prompts = [np.asarray([1, 2, 3, 4, 5], np.int32),
               np.asarray([6, 7], np.int32),
               np.asarray([8, 9, 10], np.int32),
               np.asarray([11, 12, 13, 14], np.int32)]
    budgets = [7, 13, 5, 10]
    gen = make_generator(model, max_len=48, max_new=max(budgets))
    want = [
        np.asarray(gen(params, jnp.asarray(p)[None, :]))[0, p.size: p.size + mn]
        for p, mn in zip(prompts, budgets)
    ]

    windows = {}
    for k in (1, 2, 4, 8):
        eng = _engine(model, params, decode_ahead=k)
        reqs = [eng.submit(p, max_new=mn) for p, mn in zip(prompts, budgets)]
        eng.run()
        for i, (r, w) in enumerate(zip(reqs, want)):
            assert r.status == "done"
            np.testing.assert_array_equal(
                np.asarray(r.generated), w, err_msg=f"k={k} req {i}")
        windows[k] = eng.stats.summary()["n_windows"]
    assert windows[8] < windows[4] < windows[2] < windows[1]


def _mid_window_stop(stream, windows, avoid=()):
    """Index of the first token of ``stream`` that has not occurred earlier,
    is not in ``avoid``, and does NOT end a decode window for any k in
    ``windows`` (token 0 comes from prefill; a k-window then covers indices
    1..k, k+1..2k, ...) — so arming it as EOS provably retires the row
    mid-window whatever the installation's random numbers made the stream.
    None if the stream offers no such token."""
    for i, t in enumerate(stream):
        if (i >= 1 and t not in stream[:i] and t not in avoid
                and all(i % k for k in windows)):
            return i
    return None


def test_eos_budget_retire_mid_window_and_waste_accounting():
    """A row stopping mid-window (EOS or budget) keeps tokens up to and
    including the stop, discards the ≤k−1 overrun, and the discard shows
    up in window_waste_steps — while parity with the k=1 engine holds."""
    model, params = _model_and_params(seed=4)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)

    base = _engine(model, params, decode_ahead=1)
    rb = base.submit(prompt, max_new=9)
    base.run()

    # eos_id chosen so the stop lands mid-window for both k below
    stream = [int(t) for t in rb.generated]
    stop_at = _mid_window_stop(stream, windows=(4, 8))
    if stop_at is None:
        pytest.skip(f"greedy stream {stream} has no fresh token mid-window")
    eos = stream[stop_at]

    # with eos_id set, ANY k must emit the base stream truncated at the
    # first EOS (inclusive) — no separate k=1-with-eos engine needed
    for k in (4, 8):
        engk = _engine(model, params, decode_ahead=k, eos_id=eos)
        rk = engk.submit(prompt, max_new=9)
        engk.run()
        assert rk.status == "done"
        assert list(rk.generated) == list(rb.generated[: stop_at + 1])
        assert len(rk.generated) == stop_at + 1  # EOS kept, overrun dropped
        s = engk.stats.summary()
        assert s["window_waste_steps"] > 0
        assert s["window_waste_frac"] > 0

    # budget not a multiple of k: exactly max_new tokens, never more
    eng = _engine(model, params, decode_ahead=4)
    r = eng.submit(prompt, max_new=6)  # 1 prefill token + 5 windowed
    eng.run()
    assert len(r.generated) == 6
    assert list(r.generated) == list(rb.generated[:6])
    assert eng.stats.summary()["window_waste_steps"] > 0


def test_cursor_clamps_at_max_len_under_window_overrun():
    """A tight cache (max_len == bucket + max_new) with k not dividing
    max_new forces the frozen-mask overrun to run the cursor INTO the
    clamp (models/transformer.py); output parity and the cursor cap prove
    the overrun stayed inside the row."""
    model, params = _model_and_params(seed=5)
    prompt = np.asarray([2, 7, 1], np.int32)
    bucket, max_new = 8, 6
    max_len = bucket + max_new  # zero slack: any overrun would run off
    gen = make_generator(model, max_len=max_len, max_new=max_new)
    want = np.asarray(gen(params, jnp.asarray(prompt)[None, :]))[0, 3:]
    eng4 = _engine(model, params, decode_ahead=4, max_len=max_len,
                   buckets=(bucket,))
    r4 = eng4.submit(prompt, max_new=max_new)
    eng4.run()
    np.testing.assert_array_equal(np.asarray(r4.generated), want)
    for leaf in jax.tree.leaves(eng4.cache):
        if leaf.ndim == 1 and jnp.issubdtype(leaf.dtype, jnp.integer):
            assert int(leaf.max()) <= max_len  # per-slot cursors clamped


def test_deadline_expiry_mid_flight_cancels():
    """A running request whose deadline lapses between windows is
    cancelled (partial output kept); an overlap-prefilled pending whose
    deadline lapses before a slot frees is cancelled at landing."""
    model, params = _model_and_params(seed=6)
    clock = _FakeClock()

    # running-row cancellation: the callback advances the fake clock past
    # the deadline mid-generation
    eng = _engine(model, params, decode_ahead=4, clock=clock,
                  slots=1, max_len=64)
    eng.scheduler.clock = clock

    def tick(req, tok):
        clock.t += 3.0

    r = eng.submit(np.asarray([1, 2, 3], np.int32), max_new=30,
                   deadline_s=10.0, callback=tick)
    eng.run()
    assert r.status == "cancelled"
    assert 0 < len(r.generated) < 30

    # pending-overdue: slots=1 busy with a long request; the second
    # request is overlap-prefilled behind a window, then its deadline
    # lapses before the slot frees -> cancelled at landing, never run
    clock2 = _FakeClock()
    eng2 = _engine(model, params, decode_ahead=2, clock=clock2,
                   slots=1, max_len=64)
    eng2.scheduler.clock = clock2

    def slow(req, tok):
        clock2.t += 5.0

    long = eng2.submit(np.asarray([4, 5, 6], np.int32), max_new=12,
                       callback=slow)
    short = eng2.submit(np.asarray([7, 8], np.int32), max_new=4,
                        deadline_s=8.0)
    eng2.run()
    assert long.status == "done"
    assert short.status == "cancelled"
    assert short.generated == []  # prefilled but never landed


def test_prefill_overlap_preserves_fifo_and_output():
    """With more requests than slots the engine overlap-prefills behind
    in-flight windows; completion set, per-request output, and admission
    order (FIFO) all match the no-overlap semantics."""
    model, params = _model_and_params(seed=7)
    prompts = [np.asarray([i + 1, i + 2, i + 3], np.int32) for i in range(6)]
    gen = make_generator(model, max_len=48, max_new=6)
    want = [np.asarray(gen(params, jnp.asarray(p)[None, :]))[0, 3:9]
            for p in prompts]
    eng = _engine(model, params, decode_ahead=2, slots=2)
    reqs = [eng.submit(p, max_new=6) for p in prompts]
    eng.run()
    for i, (r, w) in enumerate(zip(reqs, want)):
        assert r.status == "done", f"req {i}"
        np.testing.assert_array_equal(np.asarray(r.generated), w,
                                      err_msg=f"req {i}")
    admits = [r.admit_t for r in reqs]
    assert admits == sorted(admits)  # FIFO admission preserved


# ----------------------------------------------------------------------
# prefix cache


def test_prefix_cache_hit_skips_prefill_with_identical_output():
    """The second identical prompt hits the cache: the prefill dispatch
    count stays flat, the hit is visible in stats, and the output is
    token-identical to the cold run."""
    model, params = _model_and_params(seed=8)
    prompt = np.asarray([9, 4, 2, 6], np.int32)

    eng = _engine(model, params, decode_ahead=2, prefix_cache_bytes=64 << 20)
    calls = {"n": 0}
    real = eng._dense_prefill

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    eng._dense_prefill = counting
    r1 = eng.submit(prompt, max_new=5)
    eng.run()
    assert calls["n"] == 1
    r2 = eng.submit(prompt, max_new=5)
    r3 = eng.submit(prompt, max_new=3)  # same prompt, different budget
    eng.run()
    assert calls["n"] == 1  # both later prefills skipped
    assert list(r2.generated) == list(r1.generated)
    assert list(r3.generated) == list(r1.generated)[:3]
    s = eng.stats.summary()
    assert s["prefix_hits"] == 2 and s["prefix_misses"] == 1
    assert s["prefix_hit_rate"] == pytest.approx(2 / 3, abs=1e-4)

    # different bucket => different content address, no false hit
    sched = FIFOScheduler(max_len=64, buckets=(8, 16))
    a = sched.submit(np.arange(1, 7, dtype=np.int32), max_new=2)   # bucket 8
    b = sched.submit(np.arange(1, 12, dtype=np.int32), max_new=2)  # bucket 16
    assert a.prefix_key != b.prefix_key


def test_prefix_cache_lru_eviction_and_refusals():
    """Unit contract of the byte-bounded LRU: eviction order, oversized
    refusal — and the ISSUE 13 lift of the old greedy-only engine guard."""
    row = {"k": np.zeros((64,), np.float32)}  # 256 bytes per entry
    pc = PrefixCache(max_bytes=600)
    pc.put("a", row, 1)
    pc.put("b", row, 2)
    assert pc.get("a") is not None  # refresh a -> b is now LRU
    pc.put("c", row, 3)             # 3*256 > 600: evicts b
    assert pc.get("b") is None
    assert pc.get("a") is not None and pc.get("c") is not None
    assert pc.bytes <= 600

    big = {"k": np.zeros((1024,), np.float32)}  # 4096 bytes > budget
    pc.put("huge", big, 4)
    assert pc.get("huge") is None  # refused, cache untouched
    assert pc.get("a") is not None

    with pytest.raises(ValueError, match="max_bytes"):
        PrefixCache(max_bytes=0)

    model, params = _model_and_params(seed=9)
    # ISSUE 13 lifted the old cache+sampling refusal: the cache stores
    # only deterministic prefill products (row + logits) and every
    # admission re-picks its own first token, so this must now construct
    eng = _engine(model, params, prefix_cache_bytes=1 << 20,
                  temperature=0.7, rng=jax.random.PRNGKey(0))
    eng.close()


# ----------------------------------------------------------------------
# contracts: buckets, chaos, stats


def test_engine_scheduler_bucket_contract():
    """buckets= without a scheduler builds one; buckets= WITH a scheduler
    must agree (drift is rejected, not resolved); scheduler.max_len must
    match the engine's."""
    model, params = _model_and_params(seed=10)
    eng = _engine(model, params, buckets=(8, 16), max_len=64)
    assert eng.buckets == (8, 16)
    assert eng.scheduler.buckets == (8, 16)

    sched = FIFOScheduler(max_len=64, buckets=(8, 16))
    ok = InferenceEngine(model, params, slots=2, max_len=64,
                         scheduler=sched, buckets=(16, 8))  # order-insensitive
    assert ok.buckets == (8, 16)
    with pytest.raises(ValueError, match="buckets"):
        InferenceEngine(model, params, slots=2, max_len=64,
                        scheduler=FIFOScheduler(max_len=64, buckets=(8, 16)),
                        buckets=(8, 32))
    with pytest.raises(ValueError, match="max_len"):
        InferenceEngine(model, params, slots=2, max_len=48,
                        scheduler=FIFOScheduler(max_len=64, buckets=(8,)))


def test_chaos_serving_step_counts_windows_not_steps():
    """The serving-step chaos site consumes ONE event per window dispatch:
    a transient fault inside a decode_ahead window is absorbed by the
    watchdog with exact output parity, and the event count equals the
    window count (stable across k, so seeded plans replay)."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )

    model, params = _model_and_params(seed=11)
    prompt = np.asarray([5, 3, 1], np.int32)

    free = _engine(model, params, decode_ahead=4)
    fr = free.submit(prompt, max_new=11)
    free.run()
    clean_windows = free.stats.summary()["n_windows"]

    inj = FaultInjector(FaultPlan(seed=0, faults=(
        FaultSpec(site="serving-step", at=(1,)),)))
    eng = _engine(model, params, decode_ahead=4, chaos=inj,
                  stall_timeout_s=60.0)
    r = eng.submit(prompt, max_new=11)
    eng.run()
    assert r.status == "done"
    assert list(r.generated) == list(fr.generated)
    # one event per dispatch ATTEMPT: the clean windows + the faulted one
    assert inj.events("serving-step") == clean_windows + 1
    assert inj.summary()["faults_injected"] == 1


def test_stats_window_fields_strict_json_round_trip():
    """The new window/waste/prefix fields survive a STRICT json round trip
    (allow_nan=False — no NaN/Inf smuggled into the metrics record) and
    the ratio fields are None, not NaN, when their denominators are 0."""
    st = ServingStats(slots=3, decode_ahead=4)
    empty = st.summary()
    assert empty["window_waste_frac"] is None
    assert empty["prefix_hit_rate"] is None
    json.loads(json.dumps(empty, allow_nan=False))

    st.window(0.002, 0.001, steps=12, waste=3)
    st.window(0.001, 0.0005, steps=8, waste=0)
    st.prefix(True)
    st.prefix(False)
    st.prefix(True)
    s = st.summary()
    assert s["decode_ahead"] == 4
    assert s["n_windows"] == 2
    assert s["window_steps"] == 20
    assert s["window_waste_steps"] == 3
    assert s["window_waste_frac"] == pytest.approx(0.15)
    assert s["prefix_hits"] == 2 and s["prefix_misses"] == 1
    assert s["prefix_hit_rate"] == pytest.approx(2 / 3, abs=1e-4)
    round_tripped = json.loads(json.dumps(s, allow_nan=False))
    assert round_tripped["n_windows"] == 2


def test_engine_rejects_bad_decode_ahead():
    model, params = _model_and_params(seed=12)
    with pytest.raises(ValueError, match="decode_ahead"):
        _engine(model, params, decode_ahead=0)
