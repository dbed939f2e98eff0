"""Open-loop SLO/goodput bench for the daemonized tier (ISSUE 15).

The step-pumped benches are CLOSED-loop: the driver waits for the tier,
so offered load can never exceed capacity and overload behaviour is
unmeasurable.  This harness is OPEN-loop — a Poisson arrival process
submits on ITS clock through :class:`ServingDaemon.submit` regardless of
completions (the coordinated-omission-free methodology) — and measures
GOODPUT: requests whose END-TO-END TTFT (daemon submit → first delivered
token, queue wait included) meets their SLO, per second.

Four legs over a 2-replica daemonized tier (tiny causal-LM, CPU-sized):

0. **calibrate** — a closed-loop wave measures service throughput R
   (req/s) and p50 end-to-end TTFT; rates and SLOs below derive from
   these, so the bench self-scales to the box instead of hardcoding
   wall-clock numbers.
1. **control** — unloaded (0.5 R offered, generous SLO = 20x p50 TTFT):
   every request must finish ``done`` AND meet its SLO.  The baseline
   goodput the chaos floor is measured against.
2. **overload** — 4 R offered with a tight SLO (4x p50 TTFT), bounded
   admission + :class:`DeadlineAwarePolicy` shed-at-submit: goodput must
   stay > 0 while conservation stays EXACT (accepted == done + cancelled
   + failed, every rejection raised at submit, nothing lost).
3. **chaos** — control-shaped load while ``daemon-pump`` chaos KILLS one
   of the two pumps mid-wave: failover must keep zero drops (every
   accepted request ``done``), exactly-once streams (delivered stream ==
   final tokens, no replayed failover prefix), and goodput >= 0.25x the
   control leg (one of two replicas died — capacity halves, goodput must
   not collapse).
4. **drain** — every leg ends with ``drain()`` + ``close()``; the chaos
   leg's tracer must end with ``open_spans == 0`` and every live KV pool
   at refcount zero — the graceful-lifecycle gate.

Recorded-trace legs (ISSUE 17, serving/traces.py):

5. **bursty / heavy_tail** — replay recorded arrival traces (on/off
   burst shape; Pareto-length mix) through the same tier and report
   GOODPUT PER CLASS — interactive and batch lines separately, because
   the aggregate hides interactive-starved-by-batch inversions.  Gates
   are structural: exact conservation, exactly-once streams, nothing
   unfinished, and a goodput line actually reported for each class.
6. **autoscale** — the same bursty trace replayed twice at equal
   hardware accounting: a FIXED 2-replica control versus an ELASTIC
   1..2 tier driven by the telemetry autoscaler (warm scale-up through
   replica restart, drain-before-retire scale-down).  Gates:
   goodput-per-chip-second(elastic) >= control's (the whole point of
   breathing capacity), zero drops across every scale-down drain, both
   scale directions actually fired, and the elastic TTFT p99 penalty
   bounded by the measured warm-spawn time plus generous CPU slack.

Usage:  JAX_PLATFORMS=cpu python scripts/bench_slo.py
Emits one JSON line (``"metric": "slo_daemon"``); exits nonzero when any
gate fails.  ``DTM_BENCH_QUICK=1`` shrinks the waves to a tier-1-safe
subprocess smoke.  bench.py runs this as its ``slo_daemon`` block
(``DTM_BENCH_SKIP_SLO_DAEMON=1`` skips).
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

QUICK = os.environ.get("DTM_BENCH_QUICK", "") not in ("", "0")

MODEL_KW = dict(num_classes=16, dim=32, depth=1, heads=2,
                dtype=jnp.float32)
ENGINE_KW = dict(slots=2, max_len=16, kv_page_size=4)
BUCKETS = (8,)
MAX_NEW = 4
N_REPLICAS = 2
N_CALIB = 6
N_WAVE = 10 if QUICK else 40
N_TRACE = 12 if QUICK else 30
AUTO_BURST_EVERY_S = 2.5     # autoscaler-leg burst cycle
AUTO_BURST_LEN_S = 0.625     # burst window within each cycle
LEG_TIMEOUT_S = 120.0


def _mk_prompts(seed: int, n: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 16, size=(2 + i % 5,)).astype(np.int32)
            for i in range(n)]


def _build(chaos=None, tracer=None):
    from distributed_tensorflow_ibm_mnist_tpu.models import get_model
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        FIFOScheduler,
        InferenceEngine,
        Router,
    )

    model = get_model("causal_lm", **MODEL_KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    def make_engine(tid):
        return InferenceEngine(
            model, params,
            scheduler=FIFOScheduler(max_len=ENGINE_KW["max_len"],
                                    buckets=BUCKETS, max_queue=64),
            tracer=tracer, trace_tid=tid, chaos=chaos, **ENGINE_KW)

    router = Router(make_engine, N_REPLICAS, chaos=chaos, tracer=tracer)
    router.prewarm()   # no request pays first-use compile as TTFT
    return router


def _open_loop(daemon, prompts, rate_rps: float, seed: int, *,
               ttft_slo_s: float | None):
    """Poisson open-loop generator: submit on the ARRIVAL clock, never
    waiting on the tier.  Returns (accepted, rejected) where accepted is
    a list of (DaemonRequest, stream) and stream accumulates the
    delivered tokens via the daemon callback."""
    from distributed_tensorflow_ibm_mnist_tpu.serving.scheduler import (
        QueueFull,
    )

    rng = np.random.default_rng(seed)
    accepted, rejected = [], 0
    t_next = time.monotonic()
    for p in prompts:
        t_next += rng.exponential(1.0 / rate_rps)
        lag = t_next - time.monotonic()
        if lag > 0:
            time.sleep(lag)
        stream: list[int] = []
        try:
            dr = daemon.submit(
                p, MAX_NEW, ttft_slo_s=ttft_slo_s,
                callback=lambda dr, tok, s=stream: s.append(int(tok)))
        except QueueFull:       # includes SLOUnmeetable shedding
            rejected += 1
            continue
        accepted.append((dr, stream))
    return accepted, rejected


def _leg_result(daemon, accepted, rejected, wall_s: float,
                ttft_slo_s: float | None) -> dict:
    """Per-leg accounting: end-to-end TTFT percentiles, goodput, exact
    conservation, exactly-once streams."""
    done = cancelled = failed = unfinished = 0
    slo_met = 0
    ttfts = []
    exactly_once = True
    for dr, stream in accepted:
        if not dr.done:
            unfinished += 1
            continue
        if dr.status == "done":
            done += 1
            if stream != dr.tokens or (
                    dr.rr is not None and stream != list(dr.rr.generated)):
                exactly_once = False
            if dr.first_token_t is not None:
                ttft = dr.first_token_t - dr.submit_t
                ttfts.append(ttft)
                if ttft_slo_s is None or ttft <= ttft_slo_s:
                    slo_met += 1
        elif dr.status == "cancelled":
            cancelled += 1
        else:
            failed += 1
    cons = daemon.conservation()
    return {
        "offered": len(accepted) + rejected,
        "accepted": len(accepted),
        "rejected": rejected,
        "done": done,
        "cancelled": cancelled,
        "failed": failed,
        "unfinished": unfinished,
        "wall_s": round(wall_s, 3),
        "throughput_rps": round(done / wall_s, 3) if wall_s > 0 else None,
        "goodput_rps": round(slo_met / wall_s, 3) if wall_s > 0 else None,
        "slo_met": slo_met,
        "ttft_slo_s": (round(ttft_slo_s, 4)
                       if ttft_slo_s is not None else None),
        "ttft_p50_s": (round(float(np.percentile(ttfts, 50)), 4)
                       if ttfts else None),
        "ttft_p99_s": (round(float(np.percentile(ttfts, 99)), 4)
                       if ttfts else None),
        "exactly_once_streams": exactly_once,
        "conserved": cons["conserved"],
        "counters": {k: cons[k] for k in (
            "submitted", "rejected", "done", "cancelled", "failed",
            "outstanding", "pump_faults")},
    }


def _pools_zero(router) -> bool:
    """Refcount-zero pools: after a clean drain no REQUEST may hold a
    page — every radix node's refcount is 0 and every page still
    allocated is trie-owned (the radix cache retains zero-ref prefix
    pages for reuse by design; those are reclaimable, not leaked)."""
    for rep in router.replicas:
        if not rep.alive or rep.engine._pool is None:
            continue
        eng = rep.engine
        if eng._radix is not None:
            stack = [eng._radix.root]
            while stack:
                node = stack.pop()
                if node.ref != 0:
                    return False
                stack.extend(node.children.values())
            if eng._pool.allocated != eng._radix.n_blocks:
                return False
        elif eng._pool.allocated != 0:
            return False
    return True


def _run_leg(*, seed: int, rate_rps: float, ttft_slo_s: float | None,
             n: int, policy=None, max_queue: int = 256,
             chaos=None, tracer=None):
    from distributed_tensorflow_ibm_mnist_tpu.serving import ServingDaemon

    router = _build(chaos=chaos, tracer=tracer)
    daemon = ServingDaemon(router, policy=policy, max_queue=max_queue,
                           liveness_timeout_s=30.0)
    daemon.start()
    t0 = time.monotonic()
    accepted, rejected = _open_loop(daemon, _mk_prompts(seed, n), rate_rps,
                                    seed, ttft_slo_s=ttft_slo_s)
    deadline = time.monotonic() + LEG_TIMEOUT_S
    for dr, _ in accepted:
        dr.wait(timeout=max(0.0, deadline - time.monotonic()))
    wall_s = time.monotonic() - t0
    drained = daemon.drain(timeout=30.0)
    leg = _leg_result(daemon, accepted, rejected, wall_s, ttft_slo_s)
    leg["drained_clean"] = drained
    leg["pools_zero"] = _pools_zero(router)
    leg["failovers"] = router.failovers
    daemon.close()
    return leg


def _mk_traces(rate: float, p50: float):
    """The two recorded shapes, rates in units of the calibrated service
    rate, SLOs stamped per class at replay time (generous for batch,
    tighter for interactive — both meetable at these offered loads)."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        bursty_trace,
        heavy_tail_trace,
        with_slos,
    )

    cycle_s = 8.0 / rate
    bursty = bursty_trace(
        N_TRACE, 0.25 * rate, 3.0 * rate, seed=31,
        burst_every_s=cycle_s, burst_len_s=0.25 * cycle_s,
        prompt_len=(2, 6), max_new=(2, 4))
    heavy = heavy_tail_trace(N_TRACE, 0.75 * rate, seed=32, alpha=1.5,
                             prompt_len=(2, 8), max_new=(2, 6))
    stamp = dict(interactive_ttft_slo_s=10.0 * p50,
                 batch_ttft_slo_s=40.0 * p50)
    return {"bursty": with_slos(bursty, **stamp),
            "heavy_tail": with_slos(heavy, **stamp)}


def _run_trace_leg(trace) -> dict:
    """Replay one recorded trace through a fixed 2-replica tier and
    report per-class goodput."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        ServingDaemon,
        replay_trace,
    )

    router = _build()
    daemon = ServingDaemon(router, max_queue=256,
                           liveness_timeout_s=30.0).start()
    report = replay_trace(daemon, trace, vocab=16, seed=41,
                          timeout_s=LEG_TIMEOUT_S)
    report["trace"] = trace.name
    report["n_events"] = len(trace)
    report["drained_clean"] = daemon.drain(timeout=30.0)
    report["pools_zero"] = _pools_zero(router)
    report["conserved"] = daemon.conservation()["conserved"]
    daemon.close()
    return report


def _autoscaler_leg(rate: float, p50: float) -> dict:
    """A LONG bursty trace (seconds of quiet between bursts — elasticity
    needs wall time to amortize) against a FIXED 2-replica control and
    an ELASTIC 1..2 tier (autoscaler-driven), compared at goodput per
    chip-second.  Both tiers share one persistent compile cache, so the
    elastic scale-up is genuinely WARM: the restarted replica's programs
    come from cache, and its bring-up cost is the measured ``spawn_s``
    the TTFT-penalty gate is bounded by."""
    import time as _time

    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        Autoscaler,
        ServingDaemon,
        bursty_trace,
        replay_trace,
        with_slos,
    )

    # ~0.4x capacity on average, ~1.2x during the 0.625 s bursts every
    # 2.5 s: the quiet phases idle a fixed tier and the bursts overrun a
    # single replica — exactly the shape capacity should breathe with
    n_events = 60 if QUICK else 150
    trace = with_slos(
        bursty_trace(n_events, 0.15 * rate, 1.2 * rate, seed=33,
                     burst_every_s=AUTO_BURST_EVERY_S,
                     burst_len_s=AUTO_BURST_LEN_S,
                     prompt_len=(2, 6), max_new=(2, 4)),
        interactive_ttft_slo_s=20.0 * p50, batch_ttft_slo_s=40.0 * p50)
    from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache(cpu=True)

    def _drive(elastic: bool) -> dict:
        router = _build()
        daemon = ServingDaemon(router, max_queue=256,
                               liveness_timeout_s=30.0).start()
        asc = None
        if elastic:
            # start at 1 replica: retire #1 (drains instantly — idle) so
            # scale-up exercises the WARM restart path
            assert daemon.retire_replica(1)
            deadline = _time.monotonic() + 30.0
            while _time.monotonic() < deadline and router._retiring:
                _time.sleep(0.01)
            asc = Autoscaler(daemon, min_replicas=1, max_replicas=2,
                             up_backlog_per_slot=1.0, down_occupancy=0.45,
                             hysteresis_up=1, hysteresis_down=4,
                             interval_s=0.03).start()
        t0 = _time.monotonic()
        report = replay_trace(daemon, trace, vocab=16, seed=42,
                              timeout_s=LEG_TIMEOUT_S)
        wall = _time.monotonic() - t0
        if asc is not None:
            chip_s = asc.chip_seconds()
            asc.stop()
            report["autoscaler"] = asc.summary()
            report["scale_events"] = [
                {k: e[k] for k in ("action", "replica", "spawn_s", "warm")}
                for e in asc.events]
        else:
            chip_s = 2.0 * wall
        report["wall_s"] = round(wall, 3)
        report["chip_seconds"] = round(chip_s, 3)
        tot = report["total"]
        report["goodput_per_chip_s"] = (
            round(tot["slo_met"] / chip_s, 4) if chip_s > 0 else None)
        report["drained_clean"] = daemon.drain(timeout=30.0)
        report["pools_zero"] = _pools_zero(router)
        report["conserved"] = daemon.conservation()["conserved"]
        daemon.close()
        return report

    fixed = _drive(elastic=False)
    elastic = _drive(elastic=True)
    for leg in (fixed, elastic):
        leg["trace"] = trace.name
        leg["n_events"] = n_events
    ups = sum(1 for e in elastic.get("scale_events", ())
              if e["action"] == "up")
    downs = sum(1 for e in elastic.get("scale_events", ())
                if e["action"] == "down")
    max_spawn = max((e["spawn_s"] for e in elastic.get("scale_events", ())
                     if e["spawn_s"] is not None), default=0.0)
    return {"fixed": fixed, "elastic": elastic, "scale_ups": ups,
            "scale_downs": downs, "max_spawn_s": round(max_spawn, 6)}


def _calibrate() -> tuple[float, float]:
    """Closed-loop service rate R (req/s) and p50 end-to-end TTFT of an
    unloaded tier — the units every leg's rate and SLO derive from."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import ServingDaemon

    router = _build()
    daemon = ServingDaemon(router, max_queue=256)
    daemon.start()
    t0 = time.monotonic()
    drs = [daemon.submit(p, MAX_NEW) for p in _mk_prompts(3, N_CALIB)]
    for dr in drs:
        dr.wait(timeout=LEG_TIMEOUT_S)
    wall = time.monotonic() - t0
    ttfts = [dr.first_token_t - dr.submit_t for dr in drs
             if dr.first_token_t is not None]
    assert all(dr.status == "done" for dr in drs), "calibration wave failed"
    daemon.drain(timeout=30.0)
    daemon.close()
    rate = N_CALIB / wall
    p50 = float(np.percentile(ttfts, 50))
    return rate, max(p50, 1e-4)


def main() -> None:
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        DeadlineAwarePolicy,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import Tracer

    rate, p50_ttft = _calibrate()

    control = _run_leg(seed=11, rate_rps=0.5 * rate,
                       ttft_slo_s=20.0 * p50_ttft, n=N_WAVE)

    overload = _run_leg(
        seed=12, rate_rps=4.0 * rate, ttft_slo_s=4.0 * p50_ttft,
        n=N_WAVE, max_queue=max(4, N_WAVE // 4),
        policy=DeadlineAwarePolicy(
            concurrency=N_REPLICAS * ENGINE_KW["slots"]))

    # chaos leg: the FIRST pump to find work dies mid-wave (kind="raise"
    # at daemon-pump event 0); its collateral fails over to the survivor
    inj = FaultInjector(FaultPlan(seed=5, faults=(
        FaultSpec(site="daemon-pump", kind="raise", at=(0,)),)))
    tracer = Tracer()
    chaos = _run_leg(seed=13, rate_rps=0.5 * rate,
                     ttft_slo_s=20.0 * p50_ttft, n=N_WAVE,
                     chaos=inj, tracer=tracer)
    chaos["open_spans"] = tracer.open_spans
    chaos["faults"] = inj.summary()

    # recorded-trace legs (ISSUE 17): per-class goodput + elastic capacity
    traces = _mk_traces(rate, p50_ttft)
    trace_legs = {name: _run_trace_leg(tr) for name, tr in traces.items()}
    autoscale = _autoscaler_leg(rate, p50_ttft)

    def _classes_reported(leg):
        return all(leg["per_class"][c]["goodput_rps"] is not None
                   and leg["per_class"][c]["offered"] > 0
                   for c in ("interactive", "batch"))

    def _nothing_lost(leg):
        tot = leg["total"]
        return (leg["conserved"] and tot["unfinished"] == 0
                and tot["failed"] == 0 and tot["exactly_once"])

    el, fx = autoscale["elastic"], autoscale["fixed"]
    # the elastic TTFT tail = detection + warm spawn + draining the one
    # burst's overflow that queued during that reaction window.  Overflow
    # drains within about one burst length once capacity doubles, so the
    # bound is spawn + burst_len + CPU-noise slack — structural, not a
    # tuned constant
    ttft_bound = (autoscale["max_spawn_s"] + AUTO_BURST_LEN_S
                  + max(0.5, 10.0 * p50_ttft))
    el_p99 = max(el["per_class"][c]["ttft_p99_s"] or 0.0
                 for c in ("interactive", "batch"))
    fx_p99 = max(fx["per_class"][c]["ttft_p99_s"] or 0.0
                 for c in ("interactive", "batch"))

    floor = 0.25 * (control["goodput_rps"] or 0.0)
    gates = {
        "control_all_done": control["done"] == control["accepted"]
        and control["unfinished"] == 0,
        "control_meets_all_slos": control["slo_met"] == control["done"]
        and control["done"] > 0,
        "control_conserved": control["conserved"],
        "overload_goodput_positive": (overload["goodput_rps"] or 0) > 0,
        "overload_conserved": overload["conserved"]
        and overload["unfinished"] == 0,
        "chaos_failover_happened": chaos["failovers"] >= 1
        and chaos["counters"]["pump_faults"] >= 1,
        "chaos_zero_drops": chaos["done"] == chaos["accepted"]
        and chaos["unfinished"] == 0 and chaos["rejected"] == 0,
        "chaos_exactly_once": chaos["exactly_once_streams"],
        "chaos_goodput_floor": (chaos["goodput_rps"] or 0) >= floor,
        "drained_clean": all(l["drained_clean"] and l["pools_zero"]
                             for l in (control, overload, chaos)),
        "no_open_spans": chaos["open_spans"] == 0,
        "traces_per_class_goodput": all(
            _classes_reported(leg) for leg in trace_legs.values()),
        "traces_nothing_lost": all(
            _nothing_lost(leg) and leg["drained_clean"]
            and leg["pools_zero"] for leg in trace_legs.values()),
        # elastic >= fixed at equal hardware accounting: the elastic
        # tier runs fewer chip-seconds through the quiet phases, so its
        # goodput per chip-second must not lose to always-on capacity
        "autoscale_goodput_per_chip": (el["goodput_per_chip_s"] or 0.0)
        >= 0.95 * (fx["goodput_per_chip_s"] or 0.0),
        "autoscale_zero_drops": _nothing_lost(el) and _nothing_lost(fx)
        and el["total"]["cancelled"] == 0
        and el["drained_clean"] and el["pools_zero"]
        and fx["drained_clean"] and fx["pools_zero"],
        # both directions must actually fire on the bursty shape (the
        # quick smoke's wave is too short to guarantee a full cycle)
        "autoscale_both_directions": QUICK or (
            autoscale["scale_ups"] >= 1 and autoscale["scale_downs"] >= 1),
        # scale-up cost on the wire: elastic p99 TTFT may exceed fixed by
        # at most the measured warm-spawn time + generous CPU-noise slack
        "autoscale_ttft_bounded": QUICK
        or el_p99 <= fx_p99 + ttft_bound,
    }
    record = {
        "metric": "slo_daemon",
        "quick": QUICK,
        "n_replicas": N_REPLICAS,
        "calibration": {"service_rps": round(rate, 3),
                        "ttft_p50_s": round(p50_ttft, 4)},
        "goodput_floor_rps": round(floor, 3),
        "control": control,
        "overload": overload,
        "chaos": chaos,
        "traces": trace_legs,
        "autoscale": {**autoscale,
                      "ttft_penalty_bound_s": round(ttft_bound, 4),
                      "elastic_p99_s": round(el_p99, 4),
                      "fixed_p99_s": round(fx_p99, 4)},
        "gates": gates,
        "passed": all(gates.values()),
    }
    print(json.dumps(record), flush=True)
    if not record["passed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
