"""Aux subsystems (SURVEY.md §5): profiling, divergence detection + fault
injection, preemption, and restart-from-checkpoint recovery."""

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
from distributed_tensorflow_ibm_mnist_tpu.utils import debug as dbg
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
from distributed_tensorflow_ibm_mnist_tpu.utils.elastic import (
    PreemptionHandler,
    run_with_recovery,
)


def _cfg(**kw):
    base = dict(
        model="mlp", model_kwargs={"hidden": (32,)}, synthetic=True,
        n_train=512, n_test=128, batch_size=64, epochs=2, dp=1, quiet=True,
    )
    base.update(kw)
    return RunConfig(**base)


# ---- profiling ----

def test_trace_session_stop_is_idempotent(tmp_path):
    """TraceSession: stop() without start() is a no-op, double stop() is
    a no-op, and `active` tracks the lifecycle."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.profiling import TraceSession

    sess = TraceSession(str(tmp_path / "never_started"))
    assert not sess.active
    sess.stop()  # never started: must not raise
    assert not sess.active

    sess2 = TraceSession(str(tmp_path / "tb_trace"))
    sess2.start()
    assert sess2.active
    jnp.sum(jnp.arange(64.0)).block_until_ready()  # something to record
    sess2.stop()
    assert not sess2.active
    sess2.stop()  # second stop: swallowed, not a crash
    assert not sess2.active


def test_profile_dir_captures_fit_trace(tmp_path):
    """RunConfig.profile_dir (VERDICT.md r2 item 4): fit() writes a
    TensorBoard-profile capture of the steady-state epochs."""
    import os

    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer

    prof_dir = str(tmp_path / "prof")
    t = Trainer(_cfg(profile_dir=prof_dir, epochs=3, eval_every=3))
    t.fit()
    hits = []
    for root, _dirs, files in os.walk(prof_dir):
        hits += [os.path.join(root, f) for f in files if ".xplane." in f or f.endswith(".trace.json.gz")]
    assert hits, f"no profile artifacts under {prof_dir}"


def test_cli_profile_flag(tmp_path):
    from distributed_tensorflow_ibm_mnist_tpu.launch.cli import build_config

    cfg = build_config(["--profile", str(tmp_path / "p")])
    assert cfg.profile_dir == str(tmp_path / "p")
    # --set spelling reaches the same field
    cfg2 = build_config(["--set", f"profile_dir={tmp_path / 'q'}"])
    assert cfg2.profile_dir == str(tmp_path / "q")


# ---- debug / divergence detection ----

def test_all_finite_and_find_nonfinite():
    tree = {"a": jnp.ones((4,)), "b": {"c": jnp.zeros((2, 2))}}
    assert bool(dbg.all_finite(tree))
    bad = dbg.inject_nan(tree, "b/c")
    assert not bool(dbg.all_finite(bad))
    assert dbg.find_nonfinite(bad) == ["b/c"]
    with pytest.raises(KeyError):
        dbg.inject_nan(tree, "nope/missing")


def test_check_state_raises_with_paths():
    tree = {"w": jnp.ones((3,)), "v": jnp.ones((3,))}
    dbg.check_state(tree, step=7)  # clean: no raise
    bad = dbg.inject_nan(tree, "v")
    with pytest.raises(dbg.TrainingDiverged) as ei:
        dbg.check_state(bad, step=7)
    assert ei.value.step == 7 and ei.value.bad_leaves == ["v"]


def test_trainer_raises_on_divergence(tmp_path):
    t = Trainer(_cfg(epochs=2))
    # poison the params before the first epoch -> loss goes NaN
    t.state = t.state.replace(params=dbg.inject_nan(t.state.params, "dense_0/kernel"))
    with pytest.raises(dbg.TrainingDiverged):
        t.fit()


# ---- preemption ----

def test_preemption_checkpoints_and_exits(tmp_path):
    ckpt = str(tmp_path / "ck")
    t = Trainer(_cfg(epochs=5, checkpoint_dir=ckpt))

    class Once:
        # trigger after the first epoch completes
        calls = 0

        @property
        def triggered(self):
            Once.calls += 1
            return Once.calls >= 1

    summary = t.fit(preemption=Once())
    assert summary["preempted"] is True
    assert summary["epochs_run"] == 1
    # resume picks up from the checkpoint
    t2 = Trainer(_cfg(epochs=5, checkpoint_dir=ckpt, resume=True))
    step = t2.restore_checkpoint()
    assert step == t.steps_per_epoch


def test_preemption_handler_manual_trigger():
    with PreemptionHandler() as h:
        assert not h.triggered
        h.trigger()
        assert h.triggered


# ---- elastic recovery ----

def test_run_with_recovery_resumes_after_divergence(tmp_path):
    ckpt = str(tmp_path / "ck")
    attempts = []

    def make_trainer():
        t = Trainer(_cfg(epochs=3, checkpoint_dir=ckpt, checkpoint_every=1))
        if not attempts:
            # first attempt: poison params -> diverges in epoch 0
            t.state = t.state.replace(
                params=dbg.inject_nan(t.state.params, "dense_0/kernel")
            )
        attempts.append(1)
        return t

    summary = run_with_recovery(make_trainer, max_restarts=2)
    assert summary["restarts"] == 1
    assert len(attempts) == 2
    assert summary["epochs_run"] == 3


def test_run_with_recovery_gives_up(tmp_path):
    ckpt = str(tmp_path / "ck")

    def make_trainer():
        t = Trainer(_cfg(epochs=2, checkpoint_dir=ckpt))
        t.state = t.state.replace(params=dbg.inject_nan(t.state.params, "dense_0/kernel"))
        return t

    with pytest.raises(dbg.TrainingDiverged):
        run_with_recovery(make_trainer, max_restarts=1)


def test_metric_writer_jsonl_and_tensorboard(tmp_path):
    """MetricWriter: JSONL file round-trip + TensorBoard event emission."""
    import json

    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter

    path = tmp_path / "m.jsonl"
    tb = tmp_path / "tb"
    w = MetricWriter(path=str(path), stdout=False, tensorboard_dir=str(tb))
    w.write("epoch", step=10, loss=0.5, accuracy=0.9)
    w.write("summary", images_per_sec_per_chip=1e5)
    w.close()

    records = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in records] == ["epoch", "summary"]
    assert records[0]["step"] == 10 and records[0]["loss"] == 0.5
    assert all("t" in r for r in records)
    event_files = list(tb.rglob("*tfevents*"))
    assert event_files, "no tensorboard event files written"


def test_metric_writer_context_manager_closes_on_exception(tmp_path):
    """MetricWriter is a context manager: the file handle is released even
    when the body raises (the leak the bare-open form had)."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter

    path = tmp_path / "m.jsonl"
    with MetricWriter(path=str(path), stdout=False) as w:
        w.write("epoch", step=1, loss=0.5)
    assert w._file.closed

    with pytest.raises(RuntimeError, match="boom"):
        with MetricWriter(path=str(path), stdout=False) as w2:
            w2.write("epoch", step=2, loss=0.4)
            raise RuntimeError("boom")
    assert w2._file.closed  # closed despite the exception
    assert len(path.read_text().splitlines()) == 2  # both records landed

    # Trainer delegates: a self-built writer closes with the trainer
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    cfg = RunConfig(model="mlp", synthetic=True, n_train=64, n_test=32,
                    batch_size=32, epochs=1, quiet=True,
                    metrics_path=str(tmp_path / "t.jsonl"))
    with Trainer(cfg) as t:
        assert not t.writer._file.closed
    assert t.writer._file.closed
    # ...but never a caller-supplied one (the caller owns its lifecycle)
    shared = MetricWriter(path=str(tmp_path / "shared.jsonl"), stdout=False)
    with Trainer(cfg.replace(name="shared_writer"), writer=shared):
        pass
    assert not shared._file.closed
    shared.close()


def test_metric_writer_append_mode_survives_crash_mid_run(tmp_path):
    """ISSUE 11 satellite: the JSONL file is opened in APPEND mode, so a
    run that dies mid-stream keeps its partial record and a restarted
    run CONTINUES the same file instead of truncating it; the
    tensorboard_dir= path degrades to JSONL-only when tensorboardX is
    unimportable instead of failing the run."""
    import json
    import sys

    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter

    path = tmp_path / "crash.jsonl"
    with pytest.raises(RuntimeError, match="power cut"):
        with MetricWriter(path=str(path), stdout=False) as w:
            w.write("epoch", step=1, loss=0.9)
            raise RuntimeError("power cut")  # the crash mid-run
    # every record written before the crash is on disk (write flushes)
    assert len(path.read_text().splitlines()) == 1

    # the restarted run APPENDS — the pre-crash history survives
    with MetricWriter(path=str(path), stdout=False) as w2:
        w2.write("epoch", step=2, loss=0.7)
    records = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["step"] for r in records] == [1, 2]
    assert records[0]["loss"] == 0.9  # not truncated by the reopen

    # tensorboard_dir= with no tensorboardX: JSONL still works, no tb dir
    tb = tmp_path / "tb_missing"
    saved = sys.modules.get("tensorboardX")
    sys.modules["tensorboardX"] = None  # force the import to fail
    try:
        with MetricWriter(path=str(path), stdout=False,
                          tensorboard_dir=str(tb)) as w3:
            assert w3._tb is None
            w3.write("epoch", step=3, loss=0.5)
    finally:
        if saved is None:
            sys.modules.pop("tensorboardX", None)
        else:
            sys.modules["tensorboardX"] = saved
    assert len(path.read_text().splitlines()) == 3
    assert not tb.exists()


def test_metric_writer_close_is_idempotent_and_write_after_close_is_clear(tmp_path):
    """ISSUE 6 satellite: double close() is a no-op (components share
    writers — trainer teardown after an explicit close must not raise),
    and write() after close() is a clear RuntimeError naming the problem,
    not a ValueError from deep inside file I/O."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter

    path = tmp_path / "closed.jsonl"
    w = MetricWriter(path=str(path), stdout=False)
    w.write("epoch", step=1, loss=0.5)
    w.close()
    w.close()  # idempotent: second close must not raise

    with pytest.raises(RuntimeError, match="closed"):
        w.write("epoch", step=2, loss=0.4)
    # the failed write lost nothing that was already durable
    assert len(path.read_text().splitlines()) == 1

    # the context-manager form hits the same idempotent path
    with MetricWriter(path=str(tmp_path / "cm.jsonl"), stdout=False) as w2:
        w2.close()  # explicit close inside the body; __exit__ closes again
    with pytest.raises(RuntimeError, match="closed"):
        w2.write("late")

    # a stdout-only writer (no file) gets the same contract
    w3 = MetricWriter(stdout=False)
    w3.close()
    with pytest.raises(RuntimeError, match="closed"):
        w3.write("late")


def test_metric_writer_sanitizes_non_finite_to_null(tmp_path):
    """NaN/Infinity metric values must round-trip as STRICT JSON null, not
    json.dumps's bare NaN/Infinity tokens (invalid JSON) — including inside
    nested blocks like a record's comparison sections."""
    import json
    import math

    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter

    path = tmp_path / "nan.jsonl"
    with MetricWriter(path=str(path), stdout=False) as w:
        rec = w.write(
            "epoch", step=1, loss=float("nan"), grad_norm=float("inf"),
            ratio=float("-inf"), ok=1.5, tag="run",
            nested={"a": float("nan"), "b": [2.0, float("inf")]})
    line = path.read_text().splitlines()[0]
    parsed = json.loads(line)  # strict parse: bare NaN tokens would raise
    assert json.loads(line, parse_constant=lambda s: pytest.fail(
        f"non-finite token {s!r} leaked into the JSON")) == parsed
    assert parsed["loss"] is None and parsed["grad_norm"] is None
    assert parsed["ratio"] is None
    assert parsed["ok"] == 1.5 and parsed["tag"] == "run"
    assert parsed["nested"] == {"a": None, "b": [2.0, None]}
    # the returned record mirrors what was written
    assert rec["loss"] is None and rec["nested"]["b"][1] is None
    assert not any(
        isinstance(v, float) and not math.isfinite(v) for v in parsed.values()
        if isinstance(v, float))


def test_hostmesh_ensure_virtual_cpu_devices():
    """ensure_virtual_cpu_devices is a no-op when already satisfied and
    reports the live device count."""
    import jax

    from distributed_tensorflow_ibm_mnist_tpu.utils.hostmesh import (
        backends_initialized,
        ensure_virtual_cpu_devices,
    )

    # conftest armed an 8-device CPU platform; asking for <= that must not
    # rebuild backends (which would invalidate every live array in the suite).
    marker = jax.numpy.ones((2,))
    assert backends_initialized()
    assert ensure_virtual_cpu_devices(8) >= 8
    assert float(marker.sum()) == 2.0  # still alive => no rebuild happened
