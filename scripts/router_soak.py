"""Router soak: the multi-replica serving tier's acceptance proof (ISSUE 8).

One seeded end-to-end story, emitted as one JSON record:

1. **Train W1** — a real causal-LM :class:`Trainer` (retrieval dataset)
   runs one epoch and checkpoints; its decode params are the tier's first
   weight version.
2. **References** — a fault-free SINGLE engine (same shape as the
   replicas: paged KV + radix, ``decode_ahead=2``) generates every wave's
   expected outputs under W1 and, later, W2.  Token identity against
   these is the router's correctness bar: routing, failover, and hot swap
   must be invisible in the tokens.
3. **Wave 1 under chaos** — a :class:`Router` of 3 replicas serves 10
   requests under a seeded plan: a ``router-dispatch`` fault (one replica
   excluded for one request, retried on the next-best) and a
   ``serving-step`` fault on an engine with NO stall watchdog — the raw
   raise fails the whole replica mid-wave.  The router closes it,
   harvests the ``engine_fault`` collateral, and re-dispatches to the
   survivors.  Asserts: exactly one failover, every request ``done``,
   outputs token-identical to the W1 reference, streaming callbacks
   exactly-once per token (the cross-attempt high-water mark).
4. **Restart** — the dead replica respawns through the same factory; the
   persistent compile cache the first spawn populated makes the respawn
   warm (``spawn_s_by_replica`` records cold vs warm bring-up).
5. **Train W2, watch, hot-swap under chaos** — the trainer resumes for a
   second epoch and checkpoints W2.  Bridge requests are IN FLIGHT when
   the :class:`WeightWatcher` polls: poll 1 validates W2 through
   ``restore_latest_intact`` and starts the rollout, but a ``weight-swap``
   chaos hit aborts the first replica's swap (it re-admits on W1, the
   all-or-nothing contract) — the rollout is incomplete, so the poll
   returns None.  Poll 2 retries exactly the straggler and completes.
   Asserts: zero dropped bridge requests, every bridge output identical
   to the W1 OR W2 reference (a request decodes under one version, never
   a mix), rollout completes on poll 2.
6. **Wave 2** — 10 fresh requests after the swap: outputs token-identical
   to the W2 reference on every replica.
7. **Trace** — the shared tracer exports one timeline; asserts it
   validates clean and carries the per-replica tracks plus the
   ``replica_failed`` / ``failover_redispatch`` / ``swap_aborted`` /
   ``weight_swap`` story instants.

The ``serving-step`` kill index is CALIBRATED, not guessed: the factory
warms each fresh engine with a dummy request (so ``spawn_s`` includes the
compile family), and a throwaway engine counts how many host steps that
warmup takes — the kill lands at ``3 * warmup_steps + 4``, i.e. the
second cluster step of wave 1, on replica 1, deterministically.

Usage:  JAX_PLATFORMS=cpu python scripts/router_soak.py
Emits one line: {"metric": "router", ..., "passed": true}.
tests/test_router.py::test_router_soak_script_passes (slow) runs it; a
dropped request exits nonzero.
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

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# one engine shape everywhere — references and replicas must run the same
# program family or "token-identical" compares different machines
ENGINE_KW = dict(slots=2, max_len=24, decode_ahead=2, kv_page_size=4)
BUCKETS = (8,)
WARM_PROMPT = [1, 2, 3]
WARM_NEW = 4


def _mk_prompts(seed: int, n: int):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 16, size=(2 + i % 5,)).astype(np.int32)
               for i in range(n)]
    budgets = [3 + i % 4 for i in range(n)]
    return prompts, budgets


def _scheduler():
    from distributed_tensorflow_ibm_mnist_tpu.serving import FIFOScheduler

    return FIFOScheduler(max_len=ENGINE_KW["max_len"], buckets=BUCKETS,
                         max_queue=64)


def _engine(model, params, **kw):
    from distributed_tensorflow_ibm_mnist_tpu.serving import InferenceEngine

    return InferenceEngine(model, params, scheduler=_scheduler(),
                           **ENGINE_KW, **kw)


def _reference(model, params, prompts, budgets):
    """Fault-free single-engine outputs: the identity bar for one wave."""
    eng = _engine(model, params)
    reqs = [eng.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    eng.run()
    eng.close()
    assert all(r.status == "done" for r in reqs)
    return [list(r.generated) for r in reqs]


def _warmup_steps(model, params) -> int:
    """Count the host steps the factory's warmup request takes — the
    serving-step chaos calibration (every spawn consumes exactly this
    many serving-step events before real traffic)."""
    eng = _engine(model, params)
    eng.submit(WARM_PROMPT, max_new=WARM_NEW)
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
    eng.close()
    return steps


def train_w1(root: str):
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    cfg = RunConfig(
        name="router_soak", model="causal_lm",
        model_kwargs={"dim": 32, "depth": 1, "heads": 2, "dtype": jnp.float32},
        dataset="retrieval", dataset_kwargs={"vocab": 16, "seq_len": 32},
        n_train=128, n_test=32, batch_size=64, epochs=1, quiet=True,
        eval_batch_size=32, checkpoint_dir=os.path.join(root, "ck"),
    )
    t = Trainer(cfg)
    t.fit()
    t.save_checkpoint(wait=True)
    return cfg, t


def train_w2(cfg):
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer

    t2 = Trainer(cfg.replace(resume=True))   # restores W1, one MORE epoch
    t2.fit()
    t2.save_checkpoint(wait=True)
    return t2


def main() -> None:
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        Router,
        WeightWatcher,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import Tracer

    root = tempfile.mkdtemp(prefix="router_soak_")

    # --- phase 1: W1 + references + calibration (no chaos anywhere yet)
    cfg, t1 = train_w1(root)
    model, w1 = t1.model, t1._decode_params()
    step1 = int(np.asarray(t1.state.step))

    p1, b1 = _mk_prompts(11, 10)               # wave 1
    pb, bb = _mk_prompts(12, 3)                # bridge (in flight at swap)
    p2, b2 = _mk_prompts(13, 10)               # wave 2
    want1 = _reference(model, w1, p1, b1)
    n_warm = _warmup_steps(model, w1)

    # --- phase 2: the seeded plan.  serving-step lands on the SECOND
    # cluster step of wave 1 (3 spawns consume 3*n_warm events, then
    # cluster steps consume one per live replica: +4 = step 2, replica 1);
    # router-dispatch faults wave 1's third submit; weight-swap aborts the
    # rollout's FIRST swap attempt.
    plan = FaultPlan(seed=21, faults=(
        FaultSpec(site="serving-step", kind="transient", at=(3 * n_warm + 4,)),
        FaultSpec(site="router-dispatch", kind="io", at=(2,)),
        FaultSpec(site="weight-swap", kind="io", at=(0,)),
    ))
    inj = FaultInjector(plan)
    tracer = Tracer()
    writer = MetricWriter(path=os.path.join(root, "metrics.jsonl"),
                          stdout=False)

    from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache(cpu=True)

    def make_engine(tid):
        eng = _engine(model, w1, stall_timeout_s=None,  # raw raise => failover
                      chaos=inj, tracer=tracer, trace_tid=tid)
        # warm INSIDE the factory so spawn_s includes the compile family:
        # on an empty cache the first spawn pays cold compiles (and writes
        # the persistent cache), every later spawn reads it back — the
        # cold-vs-warm figure
        eng.submit(WARM_PROMPT, max_new=WARM_NEW)
        while eng.has_work:
            eng.step()
        return eng

    router = Router(make_engine, 3, chaos=inj, tracer=tracer, writer=writer)

    # --- phase 3: wave 1 under chaos — dispatch fault + replica kill
    streams: dict[int, list[int]] = {}
    wave1 = [router.submit(p, max_new=b,
                           callback=lambda rr, tok: streams.setdefault(
                               rr.id, []).append(int(tok)))
             for p, b in zip(p1, b1)]
    t0 = time.perf_counter()
    router.run_until_done()
    wave1_wall = time.perf_counter() - t0

    wave1_done = all(rr.status == "done" for rr in wave1)
    wave1_identical = wave1_done and all(
        list(rr.generated) == want1[i] for i, rr in enumerate(wave1))
    # exactly-once: the replayed prefix of a failed-over request is
    # suppressed, so each stream must equal its final output exactly
    exactly_once = all(
        streams.get(rr.id, []) == list(rr.generated) for rr in wave1)
    failed_idx = [r.index for r in router.replicas if r.state == "failed"]
    redispatched = sum(rr.redispatches for rr in wave1)

    # --- phase 4: restart the dead replica (warm via the compile cache)
    restart_s = router.restart(failed_idx[0]) if failed_idx else None

    # --- phase 5: W2, bridge traffic in flight, watched rollout w/ abort
    t2 = train_w2(cfg)
    w2 = t2._decode_params()
    step2 = int(np.asarray(t2.state.step))
    want2 = _reference(model, w2, p2, b2)
    bridge_w1 = _reference(model, w1, pb, bb)
    bridge_w2 = _reference(model, w2, pb, bb)

    bridge = [router.submit(p, max_new=b) for p, b in zip(pb, bb)]
    for _ in range(2):                      # bridge decode genuinely starts
        router.step()
    watcher = WeightWatcher(cfg.checkpoint_dir, t1.state, router,
                            extract=lambda s: s.params)
    poll1 = watcher.poll()                  # W2 validated; first swap aborted
    poll2 = watcher.poll()                  # straggler retried; rollout done
    router.run_until_done()

    bridge_done = all(rr.status == "done" for rr in bridge)
    bridge_ok = bridge_done and all(
        list(rr.generated) in (bridge_w1[i], bridge_w2[i])
        for i, rr in enumerate(bridge))
    rollout_ok = (poll1 is None and poll2 == step2
                  and router.swapped_steps == [step2]
                  and all(r.weight_step == step2 for r in router.replicas))

    # --- phase 6: wave 2 — every replica now serves W2
    wave2 = [router.submit(p, max_new=b) for p, b in zip(p2, b2)]
    router.run_until_done()
    wave2_identical = all(
        rr.status == "done" and list(rr.generated) == want2[i]
        for i, rr in enumerate(wave2))

    dropped = sum(rr.status != "done" for rr in router.requests)
    summary = router.summary()
    router.close()                          # emits the merged router record
    writer.close()

    # --- phase 7: the timeline must tell the whole story, validly
    trace_path = os.path.join(root, "trace.json")
    tracer.export_trace(trace_path)
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import validate_trace

    problems = validate_trace(trace_path)
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    tracks = {e["args"]["name"] for e in events
              if e.get("ph") == "M" and e.get("name") == "thread_name"}
    instants = {e["name"] for e in events if e.get("ph") == "i"}
    trace_ok = (not problems
                and {"router", "replica 0", "replica 1", "replica 2"} <= tracks
                and {"replica_spawn", "replica_failed", "failover_redispatch",
                     "dispatch_fault", "swap_aborted", "weight_swap"}
                <= instants)

    spawn_hist = summary["spawn_s_by_replica"]
    record = {
        "metric": "router",
        "n_replicas": 3,
        "router_requests": len(router.requests),
        "dropped": dropped,
        "wave1": {
            "n": len(wave1), "identical": wave1_identical,
            "exactly_once_streams": exactly_once,
            "failovers": router.failovers, "redispatched": redispatched,
            "wall_s": round(wave1_wall, 3),
        },
        "restart": {
            "replica": failed_idx[0] if failed_idx else None,
            "spawn_s": round(restart_s, 3) if restart_s is not None else None,
        },
        "hot_swap": {
            "steps": [step1, step2], "poll1": poll1, "poll2": poll2,
            "rollout_complete": rollout_ok,
            "bridge_n": len(bridge), "bridge_ok": bridge_ok,
            "watcher_polls": watcher.polls, "watcher_skipped": watcher.skipped,
        },
        "wave2": {"n": len(wave2), "identical": wave2_identical},
        "bringup": {
            # replica 0's first spawn compiled (cold when the cache dir
            # started empty) and wrote the cache; every other spawn
            # (replicas 1-2, the restart) read it back
            "cold_spawn_s": round(spawn_hist[0][0], 3),
            "warm_spawn_s": [round(s, 3)
                             for i, hist in enumerate(spawn_hist)
                             for j, s in enumerate(hist)
                             if (i, j) != (0, 0)],
            "spawn_s_by_replica": spawn_hist,
        },
        "cluster": {k: summary.get(k) for k in (
            "n_engines", "n_requests", "n_done", "n_failed", "n_cancelled",
            "n_engine_fault", "weight_swaps", "failovers",
            "tokens_generated", "n_compiled_programs")},
        "faults": inj.summary(),
        "trace": {"valid": not problems, "problems": problems,
                  "tracks": sorted(tracks), "ok": trace_ok},
        "passed": bool(
            wave1_identical and exactly_once and router.failovers == 1
            and redispatched >= 1 and bridge_ok and rollout_ok
            and wave2_identical and dropped == 0 and trace_ok),
    }
    print(json.dumps(record), flush=True)
    if not record["passed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
