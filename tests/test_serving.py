"""Continuous-batching serving engine (serving/) + stepwise decode primitives.

The decisive properties:

* PARITY — greedy decode through the engine's slot-multiplexed host loop
  (per-request bucket-padded prefill + batched ragged decode steps) is
  token-for-token identical to the one-shot compiled ``make_generator``
  episode (the ISSUE 2 acceptance pin), and the standalone
  ``make_prefill``/``make_decode_step`` primitives reproduce it too.
* LIFECYCLE — slots refill the iteration after they free (no request waits
  on another's completion), EOS retires rows early, deadlines cancel both
  queued and running requests, and the bounded queue raises backpressure.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.generate import (
    init_cache,
    make_decode_step,
    make_generator,
    make_prefill,
)
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    QueueFull,
    ServingStats,
)

KW = dict(num_classes=16, dim=64, depth=2, heads=4, dtype=jnp.float32)


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


# ----------------------------------------------------------------------
# stepwise primitives (core/generate.py)


def test_stepwise_primitives_match_one_shot_generator():
    """make_prefill + a loop of make_decode_step calls (the cache pytree
    exposed between calls) greedily decode the SAME tokens as the fused
    make_generator episode — uniform batch, scalar-cursor fast path."""
    model, params = _model_and_params(seed=1)
    prompt = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]], jnp.int32)
    max_len, max_new = 24, 8
    want = np.asarray(
        make_generator(model, max_len=max_len, max_new=max_new)(params, prompt)
    )[:, 6:]

    prefill = make_prefill(model, max_len)
    step = make_decode_step(model, max_len, ragged=False)
    cache, last = prefill(params, prompt)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    got = [np.asarray(tok)]
    for _ in range(max_new - 1):
        cache, logits = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        got.append(np.asarray(tok))
    np.testing.assert_array_equal(np.stack(got, axis=1), want)


def test_stepwise_primitives_ragged_padded_prefill():
    """The serving-shaped path: right-padded (bucketed) prefill with real
    lengths + ragged decode steps equals each row's solo decode."""
    model, params = _model_and_params(seed=2)
    prompts = [np.asarray([7, 3, 11, 2, 5], np.int32),
               np.asarray([4, 9], np.int32)]
    bucket, max_len, max_new = 8, 24, 6
    batch = np.zeros((2, bucket), np.int32)
    lens = np.asarray([p.size for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        batch[i, : p.size] = p

    prefill = make_prefill(model, max_len)
    step = make_decode_step(model, max_len, ragged=True)
    cache, last = prefill(params, jnp.asarray(batch), jnp.asarray(lens))
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    rows = [np.asarray(tok)]
    for _ in range(max_new - 1):
        cache, logits = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        rows.append(np.asarray(tok))
    got = np.stack(rows, axis=1)  # (2, max_new)

    gen = make_generator(model, max_len=max_len, max_new=max_new)
    for i, p in enumerate(prompts):
        solo = np.asarray(gen(params, jnp.asarray(p)[None, :]))[0, p.size:]
        np.testing.assert_array_equal(got[i], solo, err_msg=f"row {i}")


def test_init_cache_matches_decode_layout():
    """init_cache builds the zeroed slot cache in exactly the decode
    layout (structure, shapes, dtypes) a real prefill produces."""
    model, params = _model_and_params(seed=3, kv_cache_dtype="int8")
    zeros = init_cache(model, params, batch=3, max_len=16)
    _, vars_ = model.apply(
        {"params": params}, jnp.zeros((3, 4), jnp.int32), decode=True,
        max_len=16, ragged=True, mutable=["cache"])
    real = vars_["cache"]
    assert jax.tree.structure(zeros) == jax.tree.structure(real)
    for z, r in zip(jax.tree.leaves(zeros), jax.tree.leaves(real)):
        assert z.shape == r.shape and z.dtype == r.dtype
        assert not np.asarray(z).any()


# ----------------------------------------------------------------------
# engine parity (the acceptance pin)


def test_engine_greedy_matches_generator_token_for_token():
    """Continuous-batching greedy decode — bucket-padded per-request
    prefill, slot insert, batched ragged steps, retire+refill — produces
    EXACTLY the tokens make_generator produces for every request, even
    with more requests than slots and mixed prompt lengths/budgets."""
    model, params = _model_and_params(seed=4)
    rng = np.random.default_rng(0)
    lens = [6, 2, 4, 5, 3, 7]
    budgets = [6, 3, 8, 2, 5, 4]
    prompts = [rng.integers(1, 16, size=(n,)).astype(np.int32) for n in lens]
    max_len = 32

    eng = InferenceEngine(
        model, params, slots=2, max_len=max_len,
        scheduler=FIFOScheduler(max_len=max_len, buckets=(8,)))
    for p, mn in zip(prompts, budgets):
        eng.submit(p, max_new=mn)
    done = eng.run()
    assert len(done) == len(prompts)
    assert all(r.status == "done" for r in done)

    by_id = {r.id: r for r in done}
    for i, (p, mn) in enumerate(zip(prompts, budgets)):
        want = np.asarray(
            make_generator(model, max_len=max_len, max_new=mn)(
                params, jnp.asarray(p)[None, :]))[0, p.size:]
        np.testing.assert_array_equal(
            np.asarray(by_id[i].generated), want,
            err_msg=f"request {i} (len {p.size}, max_new {mn})")


def test_engine_eos_retires_early_and_slot_refills():
    """A request whose greedy output hits eos retires at the EOS (kept),
    the freed slot admits the next queued request, and every request still
    matches its solo generate output."""
    model, params = _model_and_params(seed=5)
    prompt = np.asarray([1, 2, 3, 4], np.int32)
    max_len, max_new = 32, 10
    free = np.asarray(
        make_generator(model, max_len=max_len, max_new=max_new)(
            params, jnp.asarray(prompt)[None, :]))[0, 4:]
    eos = int(free[2])  # a token the row certainly emits at step 2

    eng = InferenceEngine(
        model, params, slots=1, max_len=max_len, eos_id=eos,
        pad_id=int(eos == 0),
        scheduler=FIFOScheduler(max_len=max_len, buckets=(8,)))
    other = np.asarray([5, 6], np.int32)
    r0 = eng.submit(prompt, max_new=max_new)
    r1 = eng.submit(other, max_new=3)  # waits for slot 0 to free
    done = eng.run()
    assert [r.id for r in done] == [r0.id, r1.id]

    hits = np.nonzero(free == eos)[0]
    stop = int(hits[0]) + 1
    assert r0.generated[-1] == eos and len(r0.generated) == stop
    np.testing.assert_array_equal(np.asarray(r0.generated), free[:stop])
    # the refilled slot's request decoded from a CLEAN row: solo parity
    want = np.asarray(
        make_generator(model, max_len=max_len, max_new=3, eos_id=eos,
                       pad_id=int(eos == 0))(
            params, jnp.asarray(other)[None, :]))[0, 2:2 + len(r1.generated)]
    np.testing.assert_array_equal(np.asarray(r1.generated), want)


def test_engine_sampled_decode_deterministic_under_rng():
    model, params = _model_and_params(seed=6)
    prompt = np.asarray([1, 2, 3], np.int32)

    def run(key):
        eng = InferenceEngine(
            model, params, slots=1, max_len=16, temperature=1.0,
            rng=jax.random.PRNGKey(key),
            scheduler=FIFOScheduler(max_len=16, buckets=(4,)))
        eng.submit(prompt, max_new=6)
        return list(eng.run()[0].generated)

    assert run(0) == run(0)
    assert run(0) != run(7)  # with overwhelming probability
    with pytest.raises(ValueError, match="rng"):
        InferenceEngine(model, params, slots=1, max_len=16, temperature=1.0)
    with pytest.raises(ValueError, match="temperature"):
        InferenceEngine(model, params, slots=1, max_len=16, top_k=3)
    with pytest.raises(ValueError, match="pad_id"):
        InferenceEngine(model, params, slots=1, max_len=16, eos_id=0, pad_id=0)


# ----------------------------------------------------------------------
# scheduler: bucketing, backpressure, deadlines


def test_scheduler_bucketing_and_validation():
    s = FIFOScheduler(max_len=64, buckets=(8, 16, 32), max_queue=4)
    assert s.bucket_for(1) == 8 and s.bucket_for(8) == 8
    assert s.bucket_for(9) == 16 and s.bucket_for(32) == 32
    with pytest.raises(ValueError, match="bucket"):
        s.bucket_for(33)
    with pytest.raises(ValueError, match="bucket"):
        s.submit(np.arange(40), max_new=4)
    with pytest.raises(ValueError, match="max_new"):
        s.submit([1, 2], max_new=0)
    with pytest.raises(ValueError, match="cache length"):
        s.submit(np.arange(1, 31), max_new=40)  # 30 + 40 > 64
    with pytest.raises(ValueError, match="empty"):
        s.submit([], max_new=4)
    with pytest.raises(ValueError, match="exceeds max_len"):
        FIFOScheduler(max_len=16, buckets=(8, 32))


def test_engine_honors_empty_custom_scheduler():
    """An EMPTY FIFOScheduler is falsy (__len__) — the engine must still
    use it, not silently swap in a default with different buckets/bounds
    (the `scheduler or default` bug this pins)."""
    model, params = _model_and_params(seed=13)
    sched = FIFOScheduler(max_len=16, buckets=(4,), max_queue=1)
    eng = InferenceEngine(model, params, slots=1, max_len=16, scheduler=sched)
    assert eng.scheduler is sched
    eng.submit([1, 2], max_new=2)
    with pytest.raises(QueueFull, match=r"\(1\)"):
        eng.submit([3], max_new=2)
    with pytest.raises(ValueError, match="max_len"):
        InferenceEngine(model, params, slots=1, max_len=32,
                        scheduler=sched)  # mismatched cache contract


def test_scheduler_backpressure_and_fifo_order():
    s = FIFOScheduler(max_len=32, buckets=(8,), max_queue=2)
    a = s.submit([1], max_new=2)
    b = s.submit([2], max_new=2)
    with pytest.raises(QueueFull):
        s.submit([3], max_new=2)
    assert s.pop().id == a.id  # FIFO
    c = s.submit([3], max_new=2)  # space freed
    assert s.pop().id == b.id and s.pop().id == c.id
    assert s.pop() is None


def test_scheduler_deadline_cancels_queued():
    clock = _FakeClock()
    s = FIFOScheduler(max_len=32, buckets=(8,), clock=clock)
    late = s.submit([1, 2], max_new=4, deadline_s=1.0)
    live = s.submit([3], max_new=4, deadline_s=10.0)
    clock.t = 5.0  # past late's deadline, inside live's
    got = s.pop()
    assert got.id == live.id
    assert late.status == "cancelled" and s.cancelled == [late]
    with pytest.raises(ValueError, match="deadline_s"):
        s.submit([1], max_new=1, deadline_s=0.0)


def test_engine_deadline_cancels_running_row():
    """A running row past its deadline is cancelled mid-generation (partial
    output kept, status 'cancelled') while the other slot keeps decoding,
    and an overdue queued request is cancelled without ever prefilling."""
    model, params = _model_and_params(seed=7)
    clock = _FakeClock()
    eng = InferenceEngine(
        model, params, slots=2, max_len=32, clock=clock,
        scheduler=FIFOScheduler(max_len=32, buckets=(8,), clock=clock))
    doomed = eng.submit([1, 2, 3], max_new=20, deadline_s=5.0)
    survivor = eng.submit([4, 5], max_new=4)
    queued_dead = eng.submit([6], max_new=2, deadline_s=5.0)
    eng.step()   # admits doomed + survivor (slots full; queued_dead waits)
    eng.step()
    assert doomed.status == "running" and len(doomed.generated) >= 2
    clock.t = 6.0  # blow the deadlines mid-flight
    done = eng.run()
    assert doomed.status == "cancelled" and 2 <= len(doomed.generated) < 20
    assert survivor.status == "done" and len(survivor.generated) == 4
    assert queued_dead.status == "cancelled" and queued_dead.generated == []
    assert queued_dead.admit_t is None  # never prefillled
    assert {r.id for r in done} == {doomed.id, survivor.id, queued_dead.id}


# ----------------------------------------------------------------------
# stats


def test_stats_percentiles_and_summary():
    from distributed_tensorflow_ibm_mnist_tpu.serving.stats import percentiles

    pct = percentiles(list(range(1, 101)))
    assert pct["p50"] == pytest.approx(50.5)
    assert pct["p99"] == pytest.approx(99.01)
    assert percentiles([])["p95"] is None

    stats = ServingStats(slots=2)
    stats.tick(2, 1.0, decoded=True)
    stats.tick(1, 1.0, decoded=True)
    s = stats.summary()
    assert s["slot_occupancy"] == pytest.approx(0.75)
    assert s["decode_steps"] == 2 and s["n_requests"] == 0
    assert s["tokens_per_sec"] is None  # no completed window yet


def test_engine_emits_serving_record_through_metric_writer(tmp_path):
    """run() drains -> ONE 'serving' JSONL record with the metric schema
    docs/SERVING.md documents, valid strict JSON."""
    import json

    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter

    model, params = _model_and_params(seed=8)
    path = tmp_path / "serving.jsonl"
    with MetricWriter(path=str(path), stdout=False) as w:
        eng = InferenceEngine(
            model, params, slots=2, max_len=32, writer=w,
            scheduler=FIFOScheduler(max_len=32, buckets=(8,)))
        for n in (3, 5, 2):
            eng.submit(np.arange(1, n + 1, dtype=np.int32), max_new=4)
        eng.run()
    records = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in records] == ["serving"]
    rec = records[0]
    assert rec["n_requests"] == 3 and rec["n_done"] == 3
    assert rec["tokens_generated"] == 12
    assert rec["tokens_per_sec"] > 0 and 0 < rec["slot_occupancy"] <= 1
    for key in ("ttft_s_p50", "ttft_s_p95", "ttft_s_p99",
                "latency_s_p50", "latency_s_p99"):
        assert rec[key] is not None and rec[key] >= 0


def test_engine_from_trainer_end_to_end():
    """InferenceEngine.from_trainer serves a trained run through the same
    clean decode model + cast params Trainer.generate uses — outputs match
    trainer.generate token for token.  The trainer's decode params arrive
    COMMITTED to their device: prewarm() must still cover every program a
    stream dispatches (the engine commits its own state beside them — with
    uncommitted uploads the second window and the first insert recompiled)."""
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

    cfg = RunConfig(
        name="serve", model="causal_lm",
        model_kwargs={"dim": 64, "depth": 1, "heads": 4, "dtype": jnp.float32},
        dataset="retrieval", dataset_kwargs={"vocab": 16, "seq_len": 32},
        n_train=128, n_test=32, batch_size=64, epochs=1, quiet=True,
        eval_batch_size=32,
    )
    with Trainer(cfg) as t:
        t.fit()
        eng = InferenceEngine.from_trainer(
            t, slots=2, max_len=24,
            scheduler=FIFOScheduler(max_len=24, buckets=(8,)))
        assert all(leaf.committed for leaf in jax.tree.leaves(eng.params))
        eng.prewarm()
        warm = eng._compile.snapshot()
        prompt = np.asarray([2, 9, 4, 7], np.int32)
        req = eng.submit(prompt, max_new=8)
        more = [eng.submit(prompt[:n], max_new=3) for n in (1, 2, 3)]
        eng.run()
        assert all(r.status == "done" for r in more)
        post = CompileTracker.delta(eng._compile.snapshot(), warm)
        assert post["n_compiled_programs"] == 0, post["by_site"]
        want = np.asarray(t.generate(jnp.asarray(prompt)[None, :], max_new=8,
                                     max_len=24))[0, 4:]
        np.testing.assert_array_equal(np.asarray(req.generated), want)

        with pytest.raises(ValueError, match="causal"):
            InferenceEngine.from_trainer(
                Trainer(RunConfig(model="mlp", synthetic=True, n_train=64,
                                  n_test=32, batch_size=32, epochs=1,
                                  quiet=True)),
                slots=1, max_len=16)
