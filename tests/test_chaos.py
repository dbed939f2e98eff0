"""Deterministic fault injection (utils/chaos.py) + the hardening it forces:
manifest-verified checkpoints with intact-walk-back restore, retryable
recovery with backoff/window/restart records, step-granular preemption, and
per-request failure isolation in the serving engine (ISSUE 3).

The fast tests here are tier-1; the full multi-fault soak
(scripts/chaos_soak.py) runs under the ``slow`` marker.
"""

import json
import os
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.state import TrainState
from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.utils import debug as dbg
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    ChaosFault,
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.checkpoint import CheckpointManager
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
from distributed_tensorflow_ibm_mnist_tpu.utils.elastic import (
    PreemptionHandler,
    run_with_recovery,
)


def _cfg(**kw):
    base = dict(
        model="mlp", model_kwargs={"hidden": (32,), "dtype": jnp.float32},
        synthetic=True, n_train=512, n_test=128, batch_size=64, epochs=2,
        dp=1, quiet=True,
    )
    base.update(kw)
    return RunConfig(**base)


def _state(seed=0, step=0):
    model = get_model("mlp", num_classes=10, hidden=(16,))
    tx = optax.sgd(1e-2)
    state = TrainState.create(
        model, tx, jax.random.PRNGKey(seed), jnp.zeros((1, 28, 28, 1), jnp.uint8)
    )
    return state.replace(step=jnp.asarray(step, jnp.int32))


# ----------------------------------------------------------------------
# the injector itself


def test_fault_injector_deterministic_schedule():
    plan = FaultPlan(seed=3, faults=(
        FaultSpec(site="train-step", kind="nan", at=(2, 5)),
        FaultSpec(site="data-batch", kind="io", prob=0.25, max_fires=3),
    ))

    def fires(inj, site, n):
        return [inj.fire(site) is not None for _ in range(n)]

    a, b = FaultInjector(plan), FaultInjector(plan)
    assert fires(a, "train-step", 8) == fires(b, "train-step", 8) == [
        False, False, True, False, False, True, False, False]
    # seeded coin: replayable, and capped by max_fires
    pa, pb = fires(a, "data-batch", 64), fires(b, "data-batch", 64)
    assert pa == pb and sum(pa) == 3  # max_fires
    assert a.summary()["faults_injected"] == 5
    assert a.summary()["by_site"] == {"train-step": 2, "data-batch": 3}
    # schedules are per-site: consuming one site never shifts another
    c = FaultInjector(plan)
    fires(c, "data-batch", 64)
    assert fires(c, "train-step", 8) == [
        False, False, True, False, False, True, False, False]
    assert [f.event for f in c.fired if f.site == "data-batch"] == [
        f.event for f in a.fired if f.site == "data-batch"]


def test_fault_injector_rejects_unknown_sites():
    with pytest.raises(ValueError, match="unknown chaos site"):
        FaultSpec(site="nope")
    with pytest.raises(ValueError, match="unknown chaos site"):
        FaultInjector(FaultPlan()).fire("nope")
    with pytest.raises(ValueError, match="prob"):
        FaultSpec(site="train-step", prob=1.5)


def test_raise_if_fired_exception_shapes():
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="checkpoint-read", kind="io", at=(0,)),
        FaultSpec(site="serving-admit", kind="poison", at=(0,)),
    )))
    with pytest.raises(OSError, match="chaos"):
        inj.raise_if_fired("checkpoint-read", OSError)
    with pytest.raises(ChaosFault, match="serving-admit"):
        inj.raise_if_fired("serving-admit")
    inj.raise_if_fired("checkpoint-read", OSError)  # event 1: no fire


# ----------------------------------------------------------------------
# checkpoint integrity: manifests + restore_latest_intact


def test_manifest_written_and_verifies(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(_state(seed=1, step=5), wait=True)
    assert os.path.exists(tmp_path / "ck" / "manifest_5.json")
    ok, reason = mgr.verify_step(5)
    assert ok, reason
    manifest = json.loads((tmp_path / "ck" / "manifest_5.json").read_text())
    assert manifest["step"] == 5 and manifest["files"] and manifest["tree_digest"]
    mgr.close()


def _corrupt_largest_file(step_dir, mode):
    victim, vsize = None, -1
    for dirpath, _d, files in os.walk(step_dir):
        for name in files:
            p = os.path.join(dirpath, name)
            if os.path.getsize(p) > vsize:
                victim, vsize = p, os.path.getsize(p)
    assert victim is not None
    if mode == "truncate":
        with open(victim, "r+b") as f:
            f.truncate(vsize // 2)
    elif mode == "delete":
        os.remove(victim)
    elif mode == "flip":  # same size, different bytes: only the digest sees it
        with open(victim, "r+b") as f:
            data = bytearray(f.read())
            data[len(data) // 2] ^= 0xFF
            f.seek(0)
            f.write(data)


@pytest.mark.parametrize("mode", ["truncate", "delete", "flip"])
def test_restore_latest_intact_walks_past_corrupt_latest(tmp_path, mode):
    """Satellite: corrupt the LATEST on-disk step (truncated, deleted, or
    bit-flipped file => manifest mismatch) — restore lands on the previous
    intact step instead of raising."""
    mgr = CheckpointManager(str(tmp_path / "ck"))
    good = _state(seed=1, step=5)
    mgr.save(good, wait=True)
    mgr.save(_state(seed=2, step=10), wait=True)
    _corrupt_largest_file(str(tmp_path / "ck" / "10"), mode)
    restored = mgr.restore_latest_intact(_state(seed=3))
    assert int(restored.step) == 5
    for a, b in zip(jax.tree.leaves(good.params), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_restore_latest_intact_empty_step_dir_and_exhaustion(tmp_path):
    import shutil

    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(_state(seed=1, step=5), wait=True)
    mgr.save(_state(seed=2, step=10), wait=True)
    # empty-dir case: the step exists in name only
    for name in os.listdir(tmp_path / "ck" / "10"):
        p = tmp_path / "ck" / "10" / name
        shutil.rmtree(p) if p.is_dir() else os.remove(p)
    assert mgr.verify_step(10) == (False, "manifest mismatch")
    assert int(mgr.restore_latest_intact(_state(seed=3)).step) == 5
    # exhaustion: every step condemned -> FileNotFoundError with reasons
    for name in os.listdir(tmp_path / "ck" / "5"):
        p = tmp_path / "ck" / "5" / name
        shutil.rmtree(p) if p.is_dir() else os.remove(p)
    with pytest.raises(FileNotFoundError, match="no intact checkpoint"):
        mgr.restore_latest_intact(_state(seed=3))
    mgr.close()


def test_restore_latest_intact_rejects_nonfinite_state(tmp_path):
    """Restored-state validation: a checkpoint whose BYTES are intact but
    whose values are non-finite (saved mid-divergence) is demoted."""
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(_state(seed=1, step=5), wait=True)
    bad = _state(seed=2, step=10)
    bad = bad.replace(params=dbg.inject_nan(bad.params, "dense_0/kernel"))
    mgr.save(bad, wait=True)
    assert mgr.verify_step(10)[0]  # bytes are fine — validation must catch it
    assert int(mgr.restore_latest_intact(_state(seed=3)).step) == 5
    mgr.close()


def test_chaos_torn_checkpoint_write_then_intact_restore(tmp_path):
    """checkpoint-write 'torn' chaos: the save lands torn (no manifest,
    truncated bytes) and restore_latest_intact walks back past it."""
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="checkpoint-write", kind="torn", at=(1,)),
    )))
    mgr = CheckpointManager(str(tmp_path / "ck"), chaos=inj)
    mgr.save(_state(seed=1, step=5), wait=True)   # event 0: clean
    mgr.save(_state(seed=2, step=10), wait=True)  # event 1: torn
    assert not os.path.exists(tmp_path / "ck" / "manifest_10.json")
    assert int(mgr.restore_latest_intact(_state(seed=3)).step) == 5
    assert inj.summary()["by_site"] == {"checkpoint-write": 1}
    mgr.close()


def test_chaos_checkpoint_read_fault_walks_back(tmp_path):
    """A transient read fault on the newest step costs one step of
    durability (the walk-back), never the restore."""
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="checkpoint-read", kind="io", at=(0,)),
    )))
    mgr = CheckpointManager(str(tmp_path / "ck"), chaos=inj)
    mgr.save(_state(seed=1, step=5), wait=True)
    mgr.save(_state(seed=2, step=10), wait=True)
    assert int(mgr.restore_latest_intact(_state(seed=3)).step) == 5
    mgr.close()


def test_trainer_resume_survives_corrupt_latest(tmp_path):
    """Satellite end-to-end: fit() resume (and run_with_recovery on top of
    it) completes when the latest checkpoint on disk is torn."""
    cfg = _cfg(epochs=2, checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1)
    t1 = Trainer(cfg)
    t1.fit()
    spe = t1.steps_per_epoch
    _corrupt_largest_file(str(tmp_path / "ck" / str(2 * spe)), "truncate")
    t2 = Trainer(cfg.replace(resume=True, epochs=1))
    assert t2.restore_checkpoint() == spe  # walked back past the torn step
    summary = t2.fit()
    assert summary["epochs_run"] == 1
    assert int(jax.device_get(t2.state.step)) == 2 * spe


# ----------------------------------------------------------------------
# elastic recovery: retryable set, backoff window, restart record


def test_run_with_recovery_retries_oserror_and_writes_restart_record(tmp_path):
    """data-batch chaos raises OSError mid-epoch (stream path); the
    configurable retryable set restarts, and the restart is VISIBLE: a
    strict-JSON `restart` record in the metrics log (satellite)."""
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="data-batch", kind="io", at=(3,)),
    )))
    mpath = tmp_path / "m.jsonl"
    cfg = _cfg(epochs=2, checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1,
               input_mode="stream", stream_chunk=2, metrics_path=str(mpath))
    summary = run_with_recovery(
        lambda: Trainer(cfg, chaos=inj), max_restarts=2, backoff_base_s=0.0)
    assert summary["restarts"] == 1
    assert inj.summary()["by_site"] == {"data-batch": 1}
    records = [json.loads(l, parse_constant=lambda s: pytest.fail(
        f"non-strict JSON token {s!r}")) for l in mpath.read_text().splitlines()]
    restarts = [r for r in records if r["kind"] == "restart"]
    assert len(restarts) == 1
    rec = restarts[0]
    assert rec["attempt"] == 1 and rec["exception"] == "OSError"
    assert rec["resume_step"] == 0 and rec["backoff_s"] == 0.0


def test_run_with_recovery_restart_window_expires_old_restarts():
    """A restart budget WINDOW: faults spaced wider than the window never
    exhaust max_restarts (the month-long-run property); without a window
    the same fault sequence gives up (lifetime budget, as before)."""

    class StubWriter:
        def write(self, *a, **k):
            return {}

    class StubTrainer:
        steps_per_epoch = 1
        _ckpt = None
        writer = StubWriter()

        def __init__(self, outcomes):
            self.config = RunConfig(checkpoint_dir="/dev/null-ck")
            self._outcomes = outcomes

        def fit(self, preemption=None):
            out = self._outcomes.pop(0)
            if isinstance(out, BaseException):
                raise out
            return dict(out)

    clock_t = [0.0]

    def clock():
        clock_t[0] += 100.0  # failures land 100s apart
        return clock_t[0]

    def make(outcomes):
        return lambda: StubTrainer(outcomes)

    fails = [OSError("a"), OSError("b"), OSError("c"), {"ok": 1}]
    summary = run_with_recovery(
        make(list(fails)), max_restarts=1, restart_window_s=10.0,
        clock=clock, sleep=lambda s: None)
    assert summary["restarts"] == 3  # every restart's predecessor expired

    with pytest.raises(OSError):
        run_with_recovery(
            make(list(fails)), max_restarts=1, restart_window_s=None,
            clock=clock, sleep=lambda s: None)

    # non-retryable exceptions propagate immediately
    with pytest.raises(KeyError):
        run_with_recovery(make([KeyError("x")]), max_restarts=5,
                          sleep=lambda s: None)


def test_run_with_recovery_backoff_deterministic():
    slept = []
    fails = [OSError(1), OSError(2), {"done": 1}]

    class W:
        def write(self, *a, **k):
            return {}

    class T:
        steps_per_epoch = 1
        _ckpt = None
        writer = W()

        def __init__(self):
            self.config = RunConfig(checkpoint_dir="/x")
            self.fit = lambda preemption=None: (
                (_ for _ in ()).throw(fails.pop(0)) if isinstance(fails[0], BaseException)
                else dict(fails.pop(0)))

    run_with_recovery(lambda: T(), max_restarts=3, backoff_base_s=0.5,
                      sleep=slept.append)
    assert len(slept) == 2
    # exponential base with deterministic jitter in [0.5, 1.0)
    assert 0.25 <= slept[0] < 0.5 and 0.5 <= slept[1] < 1.0
    slept2 = []
    fails.extend([OSError(1), OSError(2), {"done": 1}])
    run_with_recovery(lambda: T(), max_restarts=3, backoff_base_s=0.5,
                      sleep=slept2.append)
    assert slept == slept2  # replayable


# ----------------------------------------------------------------------
# preemption: worker-thread degrade + step-granular polling


def test_preemption_handler_degrades_off_main_thread():
    """Satellite: signal.signal raises ValueError off the main thread; the
    handler must degrade to manual-trigger-only with a warning, not crash."""
    res = {}

    def target():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with PreemptionHandler() as h:
                res["installed"] = h.installed
                res["pre"] = h.triggered
                h.trigger()
                res["post"] = h.triggered
            res["warned"] = any(
                "main thread" in str(x.message) for x in w)

    th = threading.Thread(target=target)
    th.start()
    th.join(timeout=30)
    assert res == {"installed": False, "pre": False, "post": True, "warned": True}
    # on the main thread handlers still install, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with PreemptionHandler() as h:
            assert h.installed


def test_stream_preemption_polls_at_step_granularity(tmp_path):
    """preempt_poll_every: a trigger raised mid-epoch stops the stream
    epoch at the next step boundary — the checkpoint lands at a step that
    is NOT an epoch multiple, and resume picks it up."""
    cfg = _cfg(epochs=2, checkpoint_dir=str(tmp_path / "ck"),
               input_mode="stream", stream_chunk=2, preempt_poll_every=2)

    class Pre:
        triggered = True

    t = Trainer(cfg)
    assert t.steps_per_epoch == 8
    summary = t.fit(preemption=Pre())
    assert summary["preempted"] is True
    step = int(jax.device_get(t.state.step))
    assert step == 2, step  # stopped at the first poll boundary, mid-epoch
    t2 = Trainer(cfg.replace(resume=True, preempt_poll_every=0))
    assert t2.restore_checkpoint() == 2


# ----------------------------------------------------------------------
# chaos training: NaN step -> divergence -> restore -> bit-identical replay


def test_chaos_nan_step_recovery_is_bit_identical(tmp_path):
    """The training half of the ISSUE 3 acceptance pin, fast form: under a
    seeded train-step NaN fault, run_with_recovery restores the previous
    durable step, replays the ORIGINAL data schedule (absolute-epoch rng),
    and finishes in a state bit-identical to the fault-free run."""
    free_cfg = _cfg(epochs=3, checkpoint_dir=str(tmp_path / "free"),
                    checkpoint_every=1, eval_every=1)
    t_free = Trainer(free_cfg)
    t_free.fit()
    want = jax.device_get(t_free.state)

    inj = FaultInjector(FaultPlan(seed=11, faults=(
        FaultSpec(site="train-step", kind="nan", at=(1,)),
    )))
    chaos_cfg = free_cfg.replace(checkpoint_dir=str(tmp_path / "chaos"))
    summary = run_with_recovery(
        lambda: Trainer(chaos_cfg, chaos=inj), max_restarts=2,
        backoff_base_s=0.0)
    assert summary["restarts"] == 1
    assert inj.summary()["by_site"] == {"train-step": 1}

    t_check = Trainer(chaos_cfg.replace(resume=True, epochs=1))
    got = jax.device_get(t_check._ckpt.restore_latest_intact(t_check.state))
    assert int(got.step) == int(want.step) == 3 * t_free.steps_per_epoch
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(want),
            jax.tree_util.tree_leaves_with_path(got)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=str(pa))


# ----------------------------------------------------------------------
# serving: per-request isolation, watchdog, drain/close


KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)


def _serve_model(seed=0):
    model = get_model("causal_lm", **KW)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, chaos=None, **kw):
    from distributed_tensorflow_ibm_mnist_tpu.serving import FIFOScheduler, InferenceEngine

    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 24)
    return InferenceEngine(
        model, params, chaos=chaos,
        scheduler=FIFOScheduler(max_len=kw["max_len"], buckets=(8,)), **kw)


def _mixed_requests(eng, n=5, callback=None):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        prompt = rng.integers(1, 16, size=(2 + i % 4,)).astype(np.int32)
        reqs.append(eng.submit(prompt, max_new=3 + i % 3, callback=callback))
    return reqs


def test_engine_poisoned_request_fails_alone():
    """A poisoned request (prefill-time chaos) lands in terminal FAILED;
    every other request retires with output identical to the fault-free
    engine — the serving half of the acceptance pin, fast form."""
    model, params = _serve_model()
    free = _engine(model, params)
    free_reqs = _mixed_requests(free)
    free.run()
    want = {i: list(r.generated) for i, r in enumerate(free_reqs)}

    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-admit", kind="poison", at=(1,)),
    )))
    eng = _engine(model, params, chaos=inj)
    reqs = _mixed_requests(eng)
    done = eng.run()
    assert len(done) == len(reqs)
    assert reqs[1].status == "failed" and "chaos" in reqs[1].error
    assert reqs[1].generated == []
    for i, r in enumerate(reqs):
        if i == 1:
            continue
        assert r.status == "done"
        assert list(r.generated) == want[i], f"request {i}"
    s = eng.stats.summary()
    assert s["n_failed"] == 1 and s["n_done"] == len(reqs) - 1


def test_engine_raising_callback_fails_that_request_only():
    model, params = _serve_model()
    free = _engine(model, params)
    free_reqs = _mixed_requests(free)
    free.run()
    want = {i: list(r.generated) for i, r in enumerate(free_reqs)}

    streamed = []

    def cb(req, tok):
        streamed.append((req.id, tok))
        if req.id == 2 and len(req.generated) == 2:
            raise RuntimeError("user callback exploded")

    eng = _engine(model, params)
    reqs = _mixed_requests(eng, callback=cb)
    eng.run()
    assert reqs[2].status == "failed" and "exploded" in reqs[2].error
    assert len(reqs[2].generated) == 2  # partial output kept
    for i, r in enumerate(reqs):
        if i == 2:
            continue
        assert r.status == "done" and list(r.generated) == want[i], f"req {i}"
    # the callback streamed every token of every healthy request, in order
    for i, r in enumerate(reqs):
        if i != 2:
            assert [t for rid, t in streamed if rid == r.id] == list(r.generated)


def test_engine_chaos_callback_site():
    """The serving-callback chaos site fails exactly the request whose
    token delivery it poisons."""
    model, params = _serve_model()
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-callback", kind="raise", at=(0,)),
    )))
    eng = _engine(model, params, chaos=inj)
    a = eng.submit([1, 2, 3], max_new=4)
    b = eng.submit([4, 5], max_new=4)
    eng.run()
    assert a.status == "failed" and "serving-callback" in a.error
    assert b.status == "done" and len(b.generated) == 4


def test_engine_stall_watchdog_transient_and_fatal():
    from distributed_tensorflow_ibm_mnist_tpu.serving import EngineStalled

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    model, params = _serve_model()

    # transient: decode faults inside the deadline are absorbed; output
    # still matches the fault-free run exactly
    free = _engine(model, params)
    fr = free.submit([1, 2, 3], max_new=4)
    free.run()

    clock = Clock()
    eng = _engine(model, params, stall_timeout_s=5.0, clock=clock)
    eng.scheduler.clock = clock
    real = eng._window
    boom = {"n": 2}

    def flaky(*a, **k):
        if boom["n"] > 0:
            boom["n"] -= 1
            raise RuntimeError("transient device fault")
        return real(*a, **k)

    eng._window = flaky
    r = eng.submit([1, 2, 3], max_new=4)
    eng.run()
    assert r.status == "done" and list(r.generated) == list(fr.generated)

    # fatal: no progress past the deadline -> in-flight FAILED, clean raise
    clock2 = Clock()
    eng2 = _engine(model, params, stall_timeout_s=5.0, clock=clock2)
    eng2.scheduler.clock = clock2

    def always_boom(*a, **k):
        clock2.t += 3.0
        raise RuntimeError("wedged")

    eng2._window = always_boom
    r2 = eng2.submit([1, 2, 3], max_new=4)
    with pytest.raises(EngineStalled, match="no token progress"):
        eng2.run()
    assert r2.status == "failed" and "wedged" in r2.error
    assert eng2.occupied == 0  # slots were cleared: the engine is reusable

    # without a watchdog the first decode fault fails in-flight and raises
    eng3 = _engine(model, params)
    eng3._window = always_boom
    r3 = eng3.submit([1, 2], max_new=3)
    with pytest.raises(RuntimeError, match="wedged"):
        eng3.run()
    assert r3.status == "failed"


def test_engine_drain_and_close():
    model, params = _serve_model()
    eng = _engine(model, params)
    reqs = _mixed_requests(eng, n=3)
    done = eng.drain()
    assert all(r.status == "done" for r in reqs) and len(done) == 3
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit([1], max_new=1)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1], max_new=1)
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()
    eng.close()  # idempotent

    # close() with live work: running + queued requests cancel cleanly
    eng2 = _engine(model, params, slots=1)
    a = eng2.submit([1, 2], max_new=8)
    b = eng2.submit([3], max_new=2)
    eng2.step()
    assert a.status == "running"
    eng2.close()
    assert a.status == "cancelled" and len(a.generated) >= 1  # partial kept
    assert b.status == "cancelled" and b.generated == []
    assert {r.id for r in eng2.completed} == {a.id, b.id}

    # context-manager form closes on exception
    with pytest.raises(RuntimeError, match="boom"):
        with _engine(model, params) as eng3:
            eng3.submit([1], max_new=1)
            raise RuntimeError("boom")
    assert eng3._closed


def test_chaos_hooks_are_noops_when_unwired(tmp_path):
    """Zero-overhead contract: a trainer/engine built WITHOUT an injector
    holds _chaos=None, so every site is one attribute test — and no
    injector exists to consult (the structural half of the chaos_soak
    bench/assert)."""
    t = Trainer(_cfg(epochs=1))
    assert t._chaos is None
    assert t._ckpt is None or t._ckpt._chaos is None
    model, params = _serve_model()
    eng = _engine(model, params)
    assert eng._chaos is None
    t2 = Trainer(_cfg(epochs=1, checkpoint_dir=str(tmp_path / "ck")))
    assert t2._ckpt._chaos is None


@pytest.mark.slow
def test_chaos_soak_script_end_to_end():
    """The full multi-fault soak (training + serving + overhead assert),
    as a subprocess on the CPU backend."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "scripts", "chaos_soak.py")],
        capture_output=True, text=True, timeout=540, env=env)
    rec = None
    for line in out.stdout.splitlines():
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if parsed.get("metric") == "chaos":
            rec = parsed
    assert rec is not None, (out.returncode, out.stderr[-2000:])
    assert rec["passed"] is True
    assert rec["training"]["bit_identical"] is True
    assert rec["serving"]["outputs_identical"] is True
    assert rec["faults_injected"] >= 4
