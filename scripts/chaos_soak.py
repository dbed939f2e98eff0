"""Chaos soak: seeded multi-fault plans against training AND serving.

The ISSUE 3 acceptance proof, as one JSON record.  Three phases:

1. **Training soak** — a stream-mode run to completion, twice: fault-free,
   then under a seeded :class:`FaultPlan` injecting a torn checkpoint
   write, a train-step NaN, a checkpoint-read fault, and a data-batch
   I/O fault, supervised by ``run_with_recovery``.  Asserts the chaos
   run's final durable state is BIT-IDENTICAL to the fault-free run
   (restore-from-intact + absolute-epoch data schedule make recovery a
   replay, not an approximation), and reports restarts + recovery
   latency (chaos wall-clock minus fault-free wall-clock).
2. **Serving soak** — a mixed request stream through the engine, twice:
   fault-free, then under a plan injecting a poisoned request
   (``serving-admit``), a raising user callback (``serving-callback``),
   and a transient decode fault (``serving-step``, absorbed by the stall
   watchdog).  Asserts every NON-poisoned request retires ``done`` with
   byte-identical outputs, and the casualties land in terminal ``failed``.
3. **Overhead guard** — asserts the zero-overhead contract structurally
   (components built without an injector hold ``_chaos=None``: each site
   is a single attribute test, and there is no injector to consult), then
   measures it: serving steps/sec with no chaos wiring vs an empty-plan
   injector, and the integrity-manifest cost per checkpoint (digest time
   vs save time — the docs/PERFORMANCE.md figure).

Usage:  JAX_PLATFORMS=cpu python scripts/chaos_soak.py
Emits one line: {"metric": "chaos", ..., "passed": true}.
tests/test_chaos.py::test_chaos_soak_script_end_to_end (slow) runs it.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _leaves_identical(a, b) -> bool:
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    if len(la) != len(lb):
        return False
    for (pa, xa), (pb, xb) in zip(la, lb):
        if pa != pb or not np.array_equal(np.asarray(xa), np.asarray(xb)):
            return False
    return True


def training_soak(root: str) -> dict:
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
    from distributed_tensorflow_ibm_mnist_tpu.utils.elastic import run_with_recovery

    cfg = RunConfig(
        name="chaos_soak", model="mlp", model_kwargs={"hidden": (32,), "dtype": jnp.float32},
        synthetic=True, n_train=512, n_test=128, batch_size=64, epochs=4,
        dp=1, quiet=True, eval_every=1, checkpoint_every=1,
        input_mode="stream", stream_chunk=2,
        checkpoint_dir=os.path.join(root, "free"),
    )

    t0 = time.perf_counter()
    t_free = Trainer(cfg)
    t_free.fit()
    free_wall = time.perf_counter() - t0
    want = jax.device_get(t_free.state)

    # ≥ 4 distinct fault kinds on the training side alone: NaN step, torn
    # checkpoint write, checkpoint-read fault, data-batch I/O fault.  The
    # `at` indices are absolute per-site event counts (they survive
    # restarts), chosen to land mid-run.
    plan = FaultPlan(seed=7, faults=(
        FaultSpec(site="train-step", kind="nan", at=(2,)),
        FaultSpec(site="checkpoint-write", kind="torn", at=(1,)),
        FaultSpec(site="checkpoint-read", kind="io", at=(0,)),
        FaultSpec(site="data-batch", kind="io", at=(27,)),
    ))
    inj = FaultInjector(plan)
    chaos_cfg = cfg.replace(checkpoint_dir=os.path.join(root, "chaos"))
    t1 = time.perf_counter()
    summary = run_with_recovery(
        lambda: Trainer(chaos_cfg, chaos=inj), max_restarts=8,
        backoff_base_s=0.05, jitter_seed=7)
    chaos_wall = time.perf_counter() - t1

    probe = Trainer(chaos_cfg.replace(resume=True, epochs=1))
    got = jax.device_get(probe._ckpt.restore_latest_intact(probe.state))

    return {
        "bit_identical": _leaves_identical(want, got),
        "final_step": int(got.step),
        "restarts": summary["restarts"],
        "faults": inj.summary(),
        "free_wall_s": round(free_wall, 3),
        "chaos_wall_s": round(chaos_wall, 3),
        "recovery_latency_s": round(max(0.0, chaos_wall - free_wall), 3),
    }


def serving_soak() -> dict:
    from distributed_tensorflow_ibm_mnist_tpu.models import get_model
    from distributed_tensorflow_ibm_mnist_tpu.serving import FIFOScheduler, InferenceEngine
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )

    model = get_model("causal_lm", num_classes=16, dim=32, depth=1, heads=2,
                      dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 16, size=(2 + i % 5,)).astype(np.int32)
               for i in range(12)]
    budgets = [3 + i % 4 for i in range(12)]

    def build(chaos=None, stall=None):
        return InferenceEngine(
            model, params, slots=3, max_len=24, chaos=chaos,
            stall_timeout_s=stall,
            scheduler=FIFOScheduler(max_len=24, buckets=(8,), max_queue=64))

    free = build()
    free_reqs = [free.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    free.run()
    want = [list(r.generated) for r in free_reqs]

    plan = FaultPlan(seed=13, faults=(
        FaultSpec(site="serving-admit", kind="poison", at=(4,)),
        FaultSpec(site="serving-callback", kind="raise", at=(9,)),
        FaultSpec(site="serving-step", kind="transient", at=(2,)),
    ))
    inj = FaultInjector(plan)
    eng = build(chaos=inj, stall=30.0)
    streamed: list[tuple[int, int]] = []
    reqs = [eng.submit(p, max_new=b,
                       callback=lambda r, t: streamed.append((r.id, t)))
            for p, b in zip(prompts, budgets)]
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    eng.close()

    failed = [i for i, r in enumerate(reqs) if r.status == "failed"]
    fired_request_faults = sum(
        1 for f in inj.fired if f.site in ("serving-admit", "serving-callback"))
    identical = all(
        reqs[i].status == "done" and list(reqs[i].generated) == want[i]
        for i in range(len(reqs)) if i not in failed)
    return {
        "n_requests": len(reqs),
        "n_failed": len(failed),
        "failed_have_errors": all("chaos" in (reqs[i].error or "") for i in failed),
        "outputs_identical": identical and len(failed) == fired_request_faults,
        "faults": inj.summary(),
        "streamed_tokens": len(streamed),
        "wall_s": round(wall, 3),
    }


def overhead_guard(root: str) -> dict:
    from distributed_tensorflow_ibm_mnist_tpu.models import get_model
    from distributed_tensorflow_ibm_mnist_tpu.serving import FIFOScheduler, InferenceEngine
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import FaultInjector, FaultPlan
    from distributed_tensorflow_ibm_mnist_tpu.utils.checkpoint import (
        CheckpointManager,
        _digest_step_dir,
    )
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    # --- the structural assert: no injector wired => _chaos is None at
    # every site owner, so each hook is ONE attribute test and there is
    # no injector object to consult on any hot path.
    t = Trainer(RunConfig(
        model="mlp", model_kwargs={"hidden": (16,)}, synthetic=True,
        n_train=128, n_test=64, batch_size=64, epochs=1, quiet=True,
        checkpoint_dir=os.path.join(root, "ov")))
    assert t._chaos is None, "unwired Trainer must hold _chaos=None"
    assert t._ckpt._chaos is None, "unwired CheckpointManager must hold _chaos=None"

    model = get_model("causal_lm", num_classes=16, dim=32, depth=1, heads=2,
                      dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def serve(chaos):
        eng = InferenceEngine(
            model, params, slots=2, max_len=24, chaos=chaos,
            scheduler=FIFOScheduler(max_len=24, buckets=(8,)))
        for i in range(8):
            eng.submit([1 + i % 7, 2, 3], max_new=8)
        t0 = time.perf_counter()
        n = 0
        while eng.has_work:
            eng.step()
            n += 1
        return (time.perf_counter() - t0) / n

    eng_probe = InferenceEngine(
        model, params, slots=2, max_len=24,
        scheduler=FIFOScheduler(max_len=24, buckets=(8,)))
    assert eng_probe._chaos is None, "unwired engine must hold _chaos=None"

    serve(None)  # warm compiles out of the comparison
    per_step_off = serve(None)
    per_step_empty = serve(FaultInjector(FaultPlan()))

    # --- manifest overhead per checkpoint: digest time vs save time
    t.fit()
    t._ckpt.wait()
    step = t._ckpt.latest_step()
    step_dir = t._ckpt._step_path(step)
    size = sum(
        os.path.getsize(os.path.join(dp, f))
        for dp, _d, fs in os.walk(step_dir) for f in fs)
    t0 = time.perf_counter()
    _digest_step_dir(step_dir)
    digest_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    t._ckpt.save(t.state, wait=True)
    save_s = time.perf_counter() - t1

    return {
        "chaos_disabled_is_structural_noop": True,  # the asserts above
        "serve_step_ms_chaos_off": round(per_step_off * 1e3, 4),
        "serve_step_ms_chaos_empty_plan": round(per_step_empty * 1e3, 4),
        "manifest_digest_ms_per_checkpoint": round(digest_s * 1e3, 3),
        "checkpoint_bytes": size,
        "save_with_manifest_ms": round(save_s * 1e3, 3),
        "manifest_frac_of_save": round(digest_s / save_s, 4) if save_s > 0 else None,
    }


def main() -> None:
    root = tempfile.mkdtemp(prefix="chaos_soak_")
    training = training_soak(root)
    serving = serving_soak()
    overhead = overhead_guard(root)
    # distinct fault sites actually hit across both soaks
    kinds = set()
    for blob in (training["faults"], serving["faults"]):
        kinds.update(blob["by_site"].keys())
    record = {
        "metric": "chaos",
        "training": training,
        "serving": serving,
        "overhead": overhead,
        "faults_injected": (
            training["faults"]["faults_injected"]
            + serving["faults"]["faults_injected"]),
        "fault_sites_hit": sorted(kinds),
        "passed": bool(
            training["bit_identical"]
            and serving["outputs_identical"]
            and serving["failed_have_errors"]
            and overhead["chaos_disabled_is_structural_noop"]),
    }
    print(json.dumps(record), flush=True)
    if not record["passed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
