"""Headline benchmark: MNIST LeNet-5 on one TPU chip.

Measures the two BASELINE.json:2 metrics of record on the reference's own
headline task (the MNIST CNN of SURVEY.md §2.1):

* images/sec/chip — steady-state training throughput (primary metric),
  via the supported ``Trainer.measure_throughput`` API (chained epoch
  dispatches, one readback — per-epoch readbacks would measure the
  host<->device link, not the chip);
* wall-clock to 99% test accuracy — reported excluding the one-time XLA
  compile and including it under BOTH compile conditions (cold: persistent
  cache bypassed; warm: persistent cache hit), each measured in this run,
  with the cache's pre-run state recorded — so the JSON line self-describes
  its compile provenance instead of silently depending on whether a
  previous run warmed the cache (VERDICT.md r2 item 7).  (The reference's
  TF1 session had no compile stage; its per-step feed_dict overhead is
  precisely what this design removes.);

plus MFU (fraction of the chip's bf16 peak, from XLA's cost analysis of the
compiled epoch — see docs/PERFORMANCE.md for the denominator), a
``dp_sharded_update`` MULTICHIP comparison block (ZeRO-1 sharded vs
replicated weight update on a subprocess-armed dp=8 virtual mesh: step
times + the analytic per-chip comm/compute/memory model —
scripts/bench_sharded_update.py), and a ``serving`` comparison block
(continuous batching vs static one-shot batching on a mixed-length
request stream — scripts/bench_serving.py), and a ``chaos`` block (the
ISSUE 3 fault-injection soak: bit-identical training recovery + isolated
serving failures under a seeded multi-fault plan, with the zero-overhead
and manifest-cost guards — scripts/chaos_soak.py, skip with
DTM_BENCH_SKIP_CHAOS), and a ``speculative`` block (ISSUE 9: n-gram
prompt-lookup drafting + verify-window decode vs plain decode-ahead on a
repetitive-suffix stream, greedy parity enforced —
scripts/bench_speculative.py, skip with DTM_BENCH_SKIP_SPEC), and a
``tp_serving`` block (ISSUE 10: tensor-parallel serving at tp ∈ {1,2,4} —
per-chip bytes pinned at 1/tp, the dense/paged x int8 x decode_ahead x
speculative parity cross token-identical across tp, a failover replay
over disjoint tp groups — scripts/bench_tp_serving.py, skip with
DTM_BENCH_SKIP_TP), and a ``cp_serving`` block (ISSUE 20:
context-parallel serving at cp ∈ {1,2,4} — sequence-sharded paged KV
pinned at 1/cp per chip, a long prompt over the synthetic single-chip
budget served to greedy + seeded-sampled parity vs cp=1, the
cp-qualified compile census, and cp-invariant chaos event counts —
scripts/bench_cp_serving.py, skip with DTM_BENCH_SKIP_CP), and a
``train_census`` block (ROADMAP 5a: per-path
pinned compile budgets for Trainer.fit()'s program family —
scripts/bench_train_census.py, skip with DTM_BENCH_SKIP_TRAIN_CENSUS),
and a ``quant`` block (ISSUE 12: weight-only int8 decode — the
greedy-parity gate over zoo LM configs x layouts vs full precision plus
the d512 bytes-moved row — scripts/bench_decode.py --quant-only, skip
with DTM_BENCH_SKIP_QUANT), and a ``sampling`` block (ISSUE 13:
per-request temperature/top_p/seed decode — the greedy-limit and
seeded-replay token-identity gates plus the speculative
rejection-sampling acceptance/throughput figures —
scripts/bench_serving.py --sampling-only, skip with
DTM_BENCH_SKIP_SAMPLING), and an ``slo_daemon`` block (ISSUE 15: the
daemonized tier under an OPEN-loop Poisson generator — goodput under
overload with deadline shedding, a chaos pump-kill leg gating the
failover goodput floor / zero drops / exactly-once streams, and the
drain-clean lifecycle — scripts/bench_slo.py, skip with
DTM_BENCH_SKIP_SLO_DAEMON), and a ``disagg`` block (ISSUE 16: the
role-typed prefill/decode tier — short-request TTFT p99 held within
1.15x of the unloaded control (in router steps) while a long-prompt
stream saturates the prefill replica, token parity vs the monolithic
tier on the full mixed stream, a kv-handoff chaos leg gating
exactly-once streams, and the per-role compile census (decode replicas
compile zero prefill programs and vice versa) —
scripts/bench_disagg.py, skip with DTM_BENCH_SKIP_DISAGG), and a
``frontdoor`` block (ISSUE 17: the asyncio HTTP/SSE front door over the
daemonized tier — unary/SSE/direct-stream token parity, pump chaos
behind live HTTP clients with zero drops and exactly-once streams, and
admission backpressure surfacing machine-readable Retry-After hints —
scripts/bench_frontdoor.py, skip with DTM_BENCH_SKIP_FRONTDOOR), and a
``crash`` block (ISSUE 18: crash durability — a serving subprocess with
a write-ahead request journal is SIGKILLed mid-stream, the journal is
replayed into a fresh tier, and clients stitch exactly-once transcripts
across the crash; gates zero lost accepted requests, zero duplicated
tokens, token parity with an uncrashed reference, steady-state journal
overhead <=2%, and torn-tail recovery — scripts/bench_crash.py, skip
with DTM_BENCH_SKIP_CRASH).  The tp_serving, cp_serving, train_census,
quant, sampling, slo_daemon, disagg, frontdoor, crash, and
serving-subprocess gates (compile census budgets, the ISSUE 11 telemetry <=2% overhead
bar, SLO/goodput counter arithmetic) fail the bench run (exit 3) on
breach, after the record prints.

One process per chip at a time: this file's parent process never imports
jax.  The chip phases (throughput, time-to-accuracy, the LM rows) run in ONE
child (``chip_phases``), then the two compile-condition children, then the
CPU-pinned blocks — strictly one after another.  A chip phase or
compile-condition child that fails also fails the run (exit 3), after the
record prints.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., ...extras}

vs_baseline: the reference publishes no numbers (BASELINE.json:13
"published": {}), so the denominator is a documented nominal estimate of the
reference's class of system: a TF1 feed_dict MNIST CNN trainer on a
K80-class IBM-Cloud GPU worker sustains ~10k images/sec/GPU (per-step
host->device feed + PS variable RPCs bound it; SURVEY.md §3.1).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))

BASELINE_IMAGES_PER_SEC_PER_CHIP = 10_000.0  # nominal reference estimate, see docstring
TARGET_ACC = 0.99


# The bench condition as CLI-visible overrides, defined ONCE so the
# subprocess compile-measurement leg runs the exact same program shapes.
BENCH_OVERRIDES: dict = {
    "batch_size": 1024, "epochs": 15, "lr": 4e-3, "schedule": "cosine",
    "target_accuracy": TARGET_ACC, "eval_every": 1, "quiet": True,
}


def _cache_dir_nonempty(cache_dir: str | None) -> bool:
    """Whether the persistent compile cache holds ANY entries.

    Deliberately named for what it checks: entries may belong to a
    different program, so this is provenance for the chip phases'
    first-epoch figure, NOT proof they compiled warm — the warm/cold
    compile figures are therefore each measured in their own subprocess
    (r3 advisor: a nonempty dir without THIS program's entries would
    otherwise report a cold compile as compile_s_warm)."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return False
    try:
        return any(os.scandir(cache_dir))
    except OSError:
        return False


def _last_record(stdout: str, key: str, value: str) -> dict | None:
    """The last JSON line of ``stdout`` whose ``key`` equals ``value``."""
    found = None
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get(key) == value:
            found = rec
    return found


def _compile_s_in_subprocess(use_cache: bool) -> float | None:
    """compile_and_first_epoch_s of the bench program in a FRESH process.

    In-process measurement of the other compile condition is dishonest both
    ways: jax serves persistent-cache entries from an in-process memory
    layer, so "cache disabled" after a warm compile is not cold, and a
    repeat compile in the same process is warmer than any fresh run.  A
    subprocess (`launch/cli.py --throughput 1`) has no in-memory caches —
    cold really recompiles (the cache is switched off by jax's own
    ``JAX_ENABLE_COMPILATION_CACHE``, wherever it was placed), warm really
    deserializes from disk.  The child needs the chip, so it only ever runs
    while no other process of this bench holds it.  None if the subprocess
    fails — which fails the run (exit 3) after the record prints.
    """
    args = [
        sys.executable, "-m", "distributed_tensorflow_ibm_mnist_tpu.launch.cli",
        "--preset", "mnist_lenet_1chip", "--throughput", "1",
    ]
    for key, val in BENCH_OVERRIDES.items():
        args += ["--set", f"{key}={val!r}"]
    env = dict(os.environ)
    if not use_cache:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    try:
        out = subprocess.run(args, capture_output=True, text=True,
                             timeout=420, env=env, cwd=_ROOT)
    except (subprocess.SubprocessError, OSError) as e:
        print(f"bench: compile-measurement subprocess (use_cache={use_cache}) "
              f"failed: {e!r}", file=sys.stderr)
        return None
    rec = _last_record(out.stdout, "kind", "throughput")
    if rec is None:
        print(f"bench: compile-measurement subprocess (use_cache={use_cache}) "
              f"produced no throughput record (rc={out.returncode}); stderr "
              f"tail: {out.stderr[-500:]!r}", file=sys.stderr)
        return None
    return rec["compile_and_first_epoch_s"]


def chip_phases() -> int:
    """Everything that needs the chip, in ONE process of its own.

    A chip belongs to one process at a time, so the bench's parent stays
    off jax entirely and runs this in a child (``main`` spawns it), then
    the two compile-condition children, strictly one after another.
    Prints one JSON line ``{"kind": "chip_phases", ...}``; returns nonzero
    — after printing — if an LM phase raised, so a Mosaic compile failure
    of the flash kernel cannot disappear from the record.
    """
    import traceback

    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import (
        RunConfig,
        get_preset,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

    # ISSUE 6 compile accounting: install before ANY jit runs so every XLA
    # compilation in THIS process is counted (the subprocess blocks report
    # their own counts in their JSON lines).
    compile_tracker = CompileTracker.install()
    compile0 = compile_tracker.snapshot()

    # batch 1024 saturates the chip (larger batches gain nothing — the
    # model is overhead/bandwidth-bound, not MXU-bound) while a
    # cosine-annealed 4e-3 Adam still reaches 99% test acc in 2 epochs.
    #
    # DTM_BENCH_QUICK: CI smoke of the HARNESS, not a measurement — the
    # same contract the subprocess blocks already honor (bench_serving
    # et al. read the env var themselves).  The headline shrinks to a
    # tiny synthetic MLP and the compile-condition subprocesses are
    # skipped; the record carries "quick": true so nothing downstream
    # mistakes the numbers for comparable figures.
    quick = bool(os.environ.get("DTM_BENCH_QUICK"))
    cfg = get_preset("mnist_lenet_1chip").replace(**BENCH_OVERRIDES)
    if quick:
        cfg = cfg.replace(
            model="mlp", model_kwargs={"hidden": (32,)}, synthetic=True,
            n_train=512, n_test=128, batch_size=128, epochs=2,
            target_accuracy=0.2)
    cache_dir = enable_compile_cache()
    trainer = Trainer(cfg)

    # Phase 1 — steady-state throughput + MFU (public API; also warms the
    # epoch-runner compile cache and restores the fresh state afterwards).
    tput = trainer.measure_throughput(epochs=2 if quick else 10)

    # Warm the eval compile outside phase 2's timed region (same shapes).
    trainer.evaluate()

    # Phase 2 — wall-clock to 99% test accuracy from the fresh state with
    # warm caches (eval every epoch; early-stops at target).
    t0 = time.perf_counter()
    summary = trainer.fit()
    wall_excl_compile = time.perf_counter() - t0

    # Phase 3 — the round-3 long-context headline as secondary metrics:
    # S=8192 causal flash LM (RoPE), steady-state tokens/sec + real MFU
    # (analytic attention supplement).  Skippable for tight time budgets.
    # Phase 3b — the same LM at head_dim 128 (heads 4): flash attention's
    # per-score-element cost is ~6 VPU f32 ops against 4*D MXU FLOPs, so
    # doubling D halves the VPU:MXU ratio.  Reported separately so the
    # D=64 row stays comparable across rounds.
    lm = lm_d128 = None
    failed: list[str] = []
    lm_cfg = RunConfig(
        name="bench_lm8k", model="causal_lm",
        model_kwargs={"dim": 512, "depth": 4, "heads": 8, "attn": "flash"},
        dataset="retrieval",
        dataset_kwargs={"vocab": 256, "seq_len": 8192},
        n_train=64, n_test=16, batch_size=8, epochs=1, quiet=True,
        eval_batch_size=8,
    )
    if not os.environ.get("DTM_BENCH_SKIP_LM"):
        try:
            lm = Trainer(lm_cfg).measure_throughput(epochs=3)
            lm_d128 = Trainer(lm_cfg.replace(
                name="bench_lm8k_d128",
                model_kwargs={"dim": 512, "depth": 4, "heads": 4,
                              "attn": "flash"},
            )).measure_throughput(epochs=3)
        except Exception:  # phase boundary: keep the headline, fail the run
            traceback.print_exc()
            failed.append("lm_d128" if lm is not None else "lm")

    cdelta = CompileTracker.delta(compile_tracker.snapshot(), compile0)
    mk = lm_cfg.model_kwargs
    print(json.dumps({
        "kind": "chip_phases", "quick": quick, "failed": failed,
        "compile_cache_dir": cache_dir,
        "tput": tput, "summary": summary,
        "wall_excl_compile": wall_excl_compile,
        "batch_size": cfg.batch_size, "lr": cfg.lr,
        "lm": lm, "lm_d128": lm_d128,
        "lm_config": (
            f"{lm_cfg.model} dim{mk['dim']} depth{mk['depth']} "
            f"heads{mk['heads']} S={lm_cfg.dataset_kwargs['seq_len']} "
            f"causal {mk['attn']} rope b{lm_cfg.batch_size}"),
        # compile accounting for the chip process (the subprocess blocks
        # carry their own counts): a warm persistent compile cache shows
        # up here as LOWER compile seconds at the same program count
        "n_compiled_programs": cdelta["n_compiled_programs"],
        "compile_time_s": cdelta["compile_time_s"],
        "compile_by_site": cdelta["by_site"],
    }), flush=True)
    return 1 if failed else 0


def main() -> None:
    from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import (
        compile_cache_dir,
    )

    # This parent never imports jax: every phase that needs the chip runs
    # in a child, one at a time (a parent that has touched jax holds the
    # chip, and a child that needs it then fails or hangs).
    quick = bool(os.environ.get("DTM_BENCH_QUICK"))
    prewarmed = _cache_dir_nonempty(compile_cache_dir())

    # Phases 1-3 — the chip phases, in their own process (chip_phases).
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; sys.exit(bench.chip_phases())"],
        stdout=subprocess.PIPE, text=True, cwd=_ROOT)
    chip = _last_record(out.stdout, "kind", "chip_phases")
    if chip is None:
        print(f"bench: the chip phases produced no record "
              f"(rc={out.returncode}) — nothing to report", file=sys.stderr)
        sys.exit(out.returncode or 1)
    chip_gate_rc = out.returncode
    tput, summary = chip["tput"], chip["summary"]
    wall_excl_compile = chip["wall_excl_compile"]
    lm, lm_d128 = chip["lm"], chip["lm_d128"]

    # Phase 1b — BOTH compile conditions, each in its own fresh subprocess
    # (see _compile_s_in_subprocess for why in-process is dishonest in both
    # directions), now that the chip child has exited and released the
    # chip.  The chip phases' own first-epoch figure is not used for
    # either: a nonempty cache dir doesn't prove it holds THIS program's
    # entries (r3 advisor), but after the chip phases the cache certainly
    # does, so the use_cache=True subprocess really deserializes and the
    # use_cache=False one really recompiles.
    compile_s_cold = compile_s_warm = None
    if not quick:
        compile_s_cold = _compile_s_in_subprocess(use_cache=False)
        if chip["compile_cache_dir"]:
            compile_s_warm = _compile_s_in_subprocess(use_cache=True)
        if compile_s_cold is None or (
                chip["compile_cache_dir"] and compile_s_warm is None):
            chip_gate_rc = chip_gate_rc or 1

    # Phase 4 — the MULTICHIP comparison: ZeRO-1 sharded vs replicated
    # weight update on a dp=8 mesh (ISSUE 1).  Runs scripts/
    # bench_sharded_update.py in a SUBPROCESS on an 8-device virtual CPU
    # mesh so this process's accelerator backend is untouched; the block
    # reports measured step times (parity/no-regression) plus the analytic
    # per-chip comm/compute/memory model.  Skippable; never sinks the
    # headline.
    sharded = None
    if not os.environ.get("DTM_BENCH_SKIP_SHARDED"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)  # the script arms its own device count
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_sharded_update.py")],
                capture_output=True, text=True, timeout=420, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "dp_sharded_update":
                    sharded = rec
            if sharded is None:
                print(
                    f"bench: dp_sharded_update subprocess produced no record "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            print(f"bench: dp_sharded_update phase failed: {e!r}", file=sys.stderr)

    # Phase 5 — the serving comparison: continuous batching (serving/
    # engine.py) vs static one-shot batching on a mixed-length synthetic
    # request stream (ISSUE 2), plus the decode-ahead sweep (k fused
    # decode steps per host sync, parity-gated speedup) and the
    # prefix-cache cold/warm TTFT leg (ISSUE 5).  Runs
    # scripts/bench_serving.py in a SUBPROCESS on the CPU backend so this
    # process's accelerator backend is untouched; the block reports
    # sustained useful tokens/sec for every leg (identical greedy output
    # enforced), TTFT percentiles, and slot occupancy.  Skippable.  The
    # subprocess's own gates (compile census budgets, telemetry <=2%
    # overhead, SLO/goodput counter arithmetic — ISSUE 11) exit it
    # nonzero; that verdict fails THIS run (exit 3) after the record
    # prints, like the tp and train-census gates.
    serving = None
    serving_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_SERVING"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_serving.py")],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "serving":
                    serving = rec
            if serving is None or out.returncode != 0:
                serving_gate_rc = out.returncode or 1
                print(
                    f"bench: serving subprocess gate "
                    f"(rc={out.returncode}, record={serving is not None}); "
                    f"stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            serving_gate_rc = 1
            print(f"bench: serving phase failed: {e!r}", file=sys.stderr)

    # Phase 5b — the paged-KV memory model (ISSUE 7): dense vs paged+radix
    # peak concurrent sessions at a FIXED HBM budget on a shared-system-
    # prompt stream (scripts/bench_kv_paging.py in a SUBPROCESS, CPU
    # backend; greedy token parity between the legs is enforced by the
    # harness itself).  Skippable with the serving phase; never sinks the
    # headline.
    kv_paging = None
    if not os.environ.get("DTM_BENCH_SKIP_SERVING"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_kv_paging.py")],
                capture_output=True, text=True, timeout=480, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "kv_paging":
                    kv_paging = rec
            if kv_paging is None:
                print(
                    f"bench: kv_paging subprocess produced no record "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            print(f"bench: kv_paging phase failed: {e!r}", file=sys.stderr)

    # Phase 5c — tensor-parallel serving (ISSUE 10): a model exceeding one
    # (synthetic) chip's budget served at tp ∈ {1,2,4} — per-chip weight +
    # KV bytes pinned at 1/tp (±10%), the full dense/paged x int8 x
    # decode_ahead x speculative parity cross token-identical across tp,
    # and a 2-replica x 2-chip-group router failover replay.  Runs
    # scripts/bench_tp_serving.py in a SUBPROCESS on an 8-device virtual
    # CPU platform.  Skippable (DTM_BENCH_SKIP_TP); a memory/parity/
    # failover gate breach FAILS the bench run (exit 3) after the record
    # prints — sharding that changes tokens or misses its memory claim is
    # a regression, not a caveat.
    tp_serving = None
    tp_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_TP"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)  # the script arms its own devices
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_tp_serving.py")],
                capture_output=True, text=True, timeout=580, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "tp_serving":
                    tp_serving = rec
            if tp_serving is None or out.returncode != 0:
                tp_gate_rc = out.returncode or 1
                print(
                    f"bench: tp_serving subprocess "
                    f"{'produced no record' if tp_serving is None else 'FAILED (memory/parity/failover gate)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            tp_gate_rc = 1
            print(f"bench: tp_serving phase failed: {e!r}", file=sys.stderr)

    # Phase 5c2 — context-parallel serving (ISSUE 20): sequence-sharded
    # paged KV over a cp×tp mesh — per-chip KV bytes pinned at 1/cp at a
    # FIXED pool size, a long prompt exceeding the synthetic single-chip
    # budget served to exact greedy + seeded-sampled parity vs the cp=1
    # reference, the cp-qualified compile census (cold budget, zero
    # post-prewarm programs), and cp-invariant chaos event counts through
    # a disagg handoff tier.  Runs scripts/bench_cp_serving.py in a
    # SUBPROCESS on an 8-device virtual CPU platform.  Skippable
    # (DTM_BENCH_SKIP_CP); any gate breach FAILS the bench run (exit 3)
    # after the record prints.
    cp_serving = None
    cp_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_CP"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)  # the script arms its own devices
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_cp_serving.py")],
                capture_output=True, text=True, timeout=580, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "cp_serving":
                    cp_serving = rec
            if cp_serving is None or out.returncode != 0:
                cp_gate_rc = out.returncode or 1
                print(
                    f"bench: cp_serving subprocess "
                    f"{'produced no record' if cp_serving is None else 'FAILED (census/memory/parity/chaos gate)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            cp_gate_rc = 1
            print(f"bench: cp_serving phase failed: {e!r}", file=sys.stderr)

    # Phase 5d — quantized decode compute (ISSUE 12): weight-only int8
    # matmuls with fused dequant, measured two ways by scripts/
    # bench_decode.py --quant-only in a SUBPROCESS on the CPU backend:
    # the greedy-parity gate (zoo LM configs x dense/paged x decode_ahead
    # {1,8} x ±speculative vs full precision on briefly-fit weights;
    # breach exits 4) and the d512 bytes-moved row (int8+scales weight
    # stream vs f32 — the bandwidth claim emulated CPU can make
    # honestly).  Skippable (DTM_BENCH_SKIP_QUANT); a parity breach
    # FAILS the bench run (exit 3) after the record prints — quantization
    # that changes tokens past the floor is a regression, not a knob.
    quant = None
    quant_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_QUANT"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_decode.py"),
                 "--quant-only", "--reps", "3"],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "quant_decode":
                    quant = rec
            if quant is None or out.returncode != 0:
                quant_gate_rc = out.returncode or 1
                print(
                    f"bench: quant subprocess "
                    f"{'produced no record' if quant is None else 'FAILED (greedy-parity gate)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            quant_gate_rc = 1
            print(f"bench: quant phase failed: {e!r}", file=sys.stderr)

    # Phase 5e — per-request sampling (ISSUE 13): temperature/top_p/seed
    # decode measured by scripts/bench_serving.py --sampling-only in a
    # SUBPROCESS on the CPU backend: the greedy-limit gate (explicit
    # temperature=0 params token-identical to plain greedy on dense AND
    # speculative engines), the seeded-replay gate (the sampled stream
    # served twice is token-identical — a request's tokens are a pure
    # function of its seed), and the speculative rejection-sampling
    # figures (acceptance rate + useful tokens/sec beside the greedy-spec
    # floor).  Skippable (DTM_BENCH_SKIP_SAMPLING); a parity/replay gate
    # breach FAILS the bench run (exit 3) after the record prints —
    # sampling that leaks into greedy output or drifts across replays is
    # a correctness regression, not noise.
    sampling = None
    sampling_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_SAMPLING"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_serving.py"),
                 "--sampling-only"],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "sampling":
                    sampling = rec
            if sampling is None or out.returncode != 0:
                sampling_gate_rc = out.returncode or 1
                print(
                    f"bench: sampling subprocess "
                    f"{'produced no record' if sampling is None else 'FAILED (greedy-limit/replay gate)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            sampling_gate_rc = 1
            print(f"bench: sampling phase failed: {e!r}", file=sys.stderr)

    # Phase 5f — chunked prefill (ISSUE 14): InferenceEngine(
    # prefill_chunk=C) measured by scripts/bench_serving.py
    # --chunked-only in a SUBPROCESS on the CPU backend, four gates:
    # decode TPOT p99 flat (<= 1.15x a no-long-prompt control) while
    # prompts past every bucket admit chunk-by-chunk, short-request TTFT
    # p99 held, token parity vs a whole-prompt engine, and the chunk
    # program family census-pinned (chunked_repeat = ZERO compiles).
    # Skippable (DTM_BENCH_SKIP_CHUNKED); a gate breach FAILS the bench
    # run (exit 3) after the record prints — a decode stall on long
    # admissions is the regression chunking exists to prevent.
    chunked = None
    chunked_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_CHUNKED"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_serving.py"),
                 "--chunked-only"],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "chunked_prefill":
                    chunked = rec
            if chunked is None or out.returncode != 0:
                chunked_gate_rc = out.returncode or 1
                print(
                    f"bench: chunked_prefill subprocess "
                    f"{'produced no record' if chunked is None else 'FAILED (TPOT/TTFT/parity/census gate)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            chunked_gate_rc = 1
            print(f"bench: chunked_prefill phase failed: {e!r}", file=sys.stderr)

    # Phase 6 — the chaos soak (ISSUE 3): seeded multi-fault plans against
    # training (torn checkpoint write, NaN step, checkpoint-read + data-
    # batch I/O faults -> bit-identical recovery) and serving (poisoned
    # request, raising callback, transient decode fault -> identical
    # outputs for every non-poisoned request), plus the zero-overhead
    # guard for disabled chaos hooks and the manifest cost per checkpoint.
    # Runs scripts/chaos_soak.py in a SUBPROCESS on the CPU backend.
    # Skippable; never sinks the headline.
    chaos = None
    if not os.environ.get("DTM_BENCH_SKIP_CHAOS"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "chaos_soak.py")],
                capture_output=True, text=True, timeout=540, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "chaos":
                    chaos = rec
            if chaos is None:
                print(
                    f"bench: chaos subprocess produced no record "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            print(f"bench: chaos phase failed: {e!r}", file=sys.stderr)

    # Phase 7 — the router soak (ISSUE 8): 3 engine replicas behind the
    # least-loaded router, chaos killing one replica mid-wave (failover
    # re-dispatch, token-identical outputs, exactly-once streams), a live
    # weight hot-swap from a training checkpoint with the first swap
    # attempt chaos-aborted (rollout retried to completion, zero dropped
    # requests), and cold-vs-warm replica bring-up through the persistent
    # compile cache.  Runs scripts/router_soak.py in a SUBPROCESS on the
    # CPU backend; the script exits nonzero when any request drops.
    # Skippable; never sinks the headline.
    router = None
    if not os.environ.get("DTM_BENCH_SKIP_ROUTER"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "router_soak.py")],
                capture_output=True, text=True, timeout=540, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "router":
                    router = rec
            if router is None or out.returncode != 0:
                print(
                    f"bench: router subprocess "
                    f"{'produced no record' if router is None else 'FAILED (dropped requests or identity breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            print(f"bench: router phase failed: {e!r}", file=sys.stderr)

    # Phase 8 — speculative decoding (ISSUE 9): n-gram prompt-lookup
    # drafting + one verify forward per window vs plain decode-ahead at
    # the same window size, on a repetitive-suffix stream, plus the
    # low-repetition control leg.  The script exits nonzero (status 4)
    # on any greedy-parity mismatch — a speedup is only ever reported
    # over token-identical output.  Runs scripts/bench_speculative.py in
    # a SUBPROCESS on the CPU backend.  Skippable (DTM_BENCH_SKIP_SPEC);
    # never sinks the headline.
    speculative = None
    if not os.environ.get("DTM_BENCH_SKIP_SPEC"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_speculative.py")],
                capture_output=True, text=True, timeout=540, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "speculative":
                    speculative = rec
            if speculative is None or out.returncode != 0:
                print(
                    f"bench: speculative subprocess "
                    f"{'produced no record' if speculative is None else 'FAILED (greedy-parity breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            print(f"bench: speculative phase failed: {e!r}", file=sys.stderr)

    # Phase 9 — the training-side compile census (ROADMAP 5a remainder):
    # Trainer.fit() now labels its compile sites with the parallelism
    # path (train_epoch[dp4_fsdp], h2d[dp1_stream], ...) and reports
    # compile_by_site; scripts/bench_train_census.py runs one tiny fit
    # per path (dp1, stream, dp4, fsdp, sharded_update, dp2 x pp2) and
    # pins every path's per-site program counts.  A breach FAILS the
    # bench run (exit 3) after the record prints.  Skippable
    # (DTM_BENCH_SKIP_TRAIN_CENSUS); runs in a SUBPROCESS on an
    # 8-device virtual CPU platform.
    train_census = None
    census_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_TRAIN_CENSUS"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)  # the script arms its own devices
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_train_census.py")],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "train_census":
                    train_census = rec
            if train_census is None or out.returncode != 0:
                census_gate_rc = out.returncode or 1
                print(
                    f"bench: train_census subprocess "
                    f"{'produced no record' if train_census is None else 'FAILED (program-count budget breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            census_gate_rc = 1
            print(f"bench: train_census phase failed: {e!r}", file=sys.stderr)

    # Phase 10 — the daemonized-tier SLO/goodput harness (ISSUE 15): an
    # OPEN-loop Poisson generator against ServingDaemon (thread-per-
    # replica pumps, policy admission) measuring goodput under an
    # unloaded control, a 4x-capacity overload with deadline shedding,
    # and a chaos leg that kills one pump mid-wave — gating exact
    # conservation, exactly-once streams, the failover goodput floor,
    # and a drain that leaves zero open spans and refcount-zero pools.
    # A breach FAILS the bench run (exit 3) after the record prints.
    # Runs scripts/bench_slo.py in a SUBPROCESS on the CPU backend.
    # Skippable (DTM_BENCH_SKIP_SLO_DAEMON).
    slo_daemon = None
    slo_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_SLO_DAEMON"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_slo.py")],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "slo_daemon":
                    slo_daemon = rec
            if slo_daemon is None or out.returncode != 0:
                slo_gate_rc = out.returncode or 1
                print(
                    f"bench: slo_daemon subprocess "
                    f"{'produced no record' if slo_daemon is None else 'FAILED (goodput/conservation/drain gate breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            slo_gate_rc = 1
            print(f"bench: slo_daemon phase failed: {e!r}", file=sys.stderr)

    # role-typed prefill/decode tier (ISSUE 16): a deterministic drip
    # driver gates short-request TTFT flatness (router steps) under a
    # saturating long-prompt stream, token parity vs the monolithic
    # tier, kv-handoff chaos exactly-once, and the per-role compile
    # census (decode replicas compile zero prefill programs and vice
    # versa).  A breach FAILS the bench run (exit 3) after the record
    # prints.  Runs scripts/bench_disagg.py in a SUBPROCESS on the CPU
    # backend.  Skippable (DTM_BENCH_SKIP_DISAGG).
    disagg = None
    disagg_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_DISAGG"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_disagg.py")],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "disagg":
                    disagg = rec
            if disagg is None or out.returncode != 0:
                disagg_gate_rc = out.returncode or 1
                print(
                    f"bench: disagg subprocess "
                    f"{'produced no record' if disagg is None else 'FAILED (TTFT/parity/chaos/census gate breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            disagg_gate_rc = 1
            print(f"bench: disagg phase failed: {e!r}", file=sys.stderr)

    # internet-shaped front door (ISSUE 17): the asyncio protocol server
    # over the daemonized tier — HTTP/SSE parity with direct daemon
    # streams, pump chaos behind live HTTP clients (zero drops,
    # exactly-once), and admission backpressure surfacing machine-
    # readable Retry-After hints end-to-end.  A breach FAILS the bench
    # run (exit 3) after the record prints.  Runs
    # scripts/bench_frontdoor.py in a SUBPROCESS on the CPU backend.
    # Skippable (DTM_BENCH_SKIP_FRONTDOOR).
    frontdoor = None
    frontdoor_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_FRONTDOOR"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_frontdoor.py")],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "frontdoor":
                    frontdoor = rec
            if frontdoor is None or out.returncode != 0:
                frontdoor_gate_rc = out.returncode or 1
                print(
                    f"bench: frontdoor subprocess "
                    f"{'produced no record' if frontdoor is None else 'FAILED (parity/chaos/backpressure gate breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            frontdoor_gate_rc = 1
            print(f"bench: frontdoor phase failed: {e!r}", file=sys.stderr)

    # crash durability (ISSUE 18): the write-ahead request journal under
    # a real SIGKILL — a serving subprocess is killed mid-stream, the
    # journal is replayed into a fresh tier, and clients stitch exactly-
    # once transcripts across the crash (zero lost accepted requests,
    # zero duplicated tokens, token parity with an uncrashed reference).
    # Also gates steady-state journal overhead <= 2% and torn-tail
    # recovery.  A breach FAILS the bench run (exit 3) after the record
    # prints.  Runs scripts/bench_crash.py in a SUBPROCESS on the CPU
    # backend.  Skippable (DTM_BENCH_SKIP_CRASH).
    crash = None
    crash_gate_rc = 0
    if not os.environ.get("DTM_BENCH_SKIP_CRASH"):
        try:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "bench_crash.py")],
                capture_output=True, text=True, timeout=560, env=env,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("metric") == "crash":
                    crash = rec
            if crash is None or out.returncode != 0:
                crash_gate_rc = out.returncode or 1
                print(
                    f"bench: crash subprocess "
                    f"{'produced no record' if crash is None else 'FAILED (durability/exactly-once/overhead gate breach)'} "
                    f"(rc={out.returncode}); stderr tail: {out.stderr[-500:]!r}",
                    file=sys.stderr,
                )
        except Exception as e:
            crash_gate_rc = 1
            print(f"bench: crash phase failed: {e!r}", file=sys.stderr)

    result = {
        "metric": "mnist_lenet5_images_per_sec_per_chip",
        "value": tput["images_per_sec_per_chip"],
        "unit": "images/sec/chip",
        "vs_baseline": round(
            tput["images_per_sec_per_chip"] / BASELINE_IMAGES_PER_SEC_PER_CHIP, 3
        ),
        "mfu": tput["mfu"],
        "model_tflops_per_sec_per_chip": tput["model_tflops_per_sec_per_chip"],
        "best_test_accuracy": summary["best_test_accuracy"],
        "target_accuracy": TARGET_ACC,
        "time_to_target_s_excl_compile": (
            round(wall_excl_compile, 3) if summary["time_to_target_s"] else None
        ),
        # both compile conditions, each measured in its own fresh
        # subprocess THIS run (see phase 1b); compile_cache_prewarmed
        # records whether the cache dir held any entries (of any program)
        # when this process started — provenance, not a warmth claim
        "time_to_target_s_incl_compile_cold": (
            round(wall_excl_compile + compile_s_cold, 3)
            if summary["time_to_target_s"] and compile_s_cold is not None
            else None
        ),
        "time_to_target_s_incl_compile_warm": (
            round(wall_excl_compile + compile_s_warm, 3)
            if summary["time_to_target_s"] and compile_s_warm is not None
            else None
        ),
        "compile_s_cold": (
            round(compile_s_cold, 3) if compile_s_cold is not None else None
        ),
        "compile_s_warm": (
            round(compile_s_warm, 3) if compile_s_warm is not None else None
        ),
        "compile_cache_prewarmed": prewarmed,
        "north_star_target_s": 60.0,
        "epochs_run": summary["epochs_run"],
        "throughput_epochs": tput["epochs"],
        # measurement condition (deviates from the BASELINE.json:8 preset's
        # batch=128 on purpose — the metric of record is images/sec/chip and
        # time-to-99%, and batch is a free knob of the rebuild, not the task):
        "batch_size": chip["batch_size"],
        "lr": chip["lr"],
        "device": tput["device"],
        "param_count": summary["param_count"],
        "quick": quick,
    }
    if lm is not None:
        result["lm_tokens_per_sec_per_chip"] = lm.get("tokens_per_sec_per_chip")
        result["lm_mfu"] = lm.get("mfu")
        result["lm_config"] = chip["lm_config"]
    if lm_d128 is not None:
        result["lm_d128_tokens_per_sec_per_chip"] = lm_d128.get(
            "tokens_per_sec_per_chip")
        result["lm_d128_mfu"] = lm_d128.get("mfu")
        result["lm_d128_config"] = "same LM at heads4 (head_dim 128)"
    if sharded is not None:
        # the dp_sharded_update comparison block (metric key dropped:
        # nested under its own name already)
        result["dp_sharded_update"] = {
            k: v for k, v in sharded.items() if k != "metric"
        }
    if serving is not None:
        result["serving"] = {
            k: v for k, v in serving.items() if k != "metric"
        }
    if kv_paging is not None:
        result["kv_paging"] = {
            k: v for k, v in kv_paging.items() if k != "metric"
        }
    if chaos is not None:
        result["chaos"] = {
            k: v for k, v in chaos.items() if k != "metric"
        }
    if router is not None:
        result["router"] = {
            k: v for k, v in router.items() if k != "metric"
        }
    if speculative is not None:
        result["speculative"] = {
            k: v for k, v in speculative.items() if k != "metric"
        }
    if tp_serving is not None:
        result["tp_serving"] = {
            k: v for k, v in tp_serving.items() if k != "metric"
        }
    if cp_serving is not None:
        result["cp_serving"] = {
            k: v for k, v in cp_serving.items() if k != "metric"
        }
    if train_census is not None:
        result["train_census"] = {
            k: v for k, v in train_census.items() if k != "metric"
        }
    if quant is not None:
        result["quant"] = {
            k: v for k, v in quant.items() if k != "metric"
        }
    if sampling is not None:
        result["sampling"] = {
            k: v for k, v in sampling.items() if k != "metric"
        }
    if chunked is not None:
        result["chunked_prefill"] = {
            k: v for k, v in chunked.items() if k != "metric"
        }
    if slo_daemon is not None:
        result["slo_daemon"] = {
            k: v for k, v in slo_daemon.items() if k != "metric"
        }
    if disagg is not None:
        result["disagg"] = {
            k: v for k, v in disagg.items() if k != "metric"
        }
    if frontdoor is not None:
        result["frontdoor"] = {
            k: v for k, v in frontdoor.items() if k != "metric"
        }
    if crash is not None:
        result["crash"] = {
            k: v for k, v in crash.items() if k != "metric"
        }
    # compile accounting of the chip process (phases 1/2/3 — the
    # subprocess blocks carry their own counts)
    result["n_compiled_programs"] = chip["n_compiled_programs"]
    result["compile_time_s"] = chip["compile_time_s"]
    result["compile_by_site"] = chip["compile_by_site"]
    if chip["failed"]:
        result["failed_chip_phases"] = chip["failed"]
    print(json.dumps(result), flush=True)
    # the hard gates (a chip phase or compile-condition child that failed;
    # tp memory/parity/failover, train compile census, serving: compile
    # budgets + telemetry overhead + SLO/goodput arithmetic) fail the RUN,
    # not just their block — after the record prints so the numbers are
    # never lost with the verdict
    if (chip_gate_rc or tp_gate_rc or cp_gate_rc or census_gate_rc
            or serving_gate_rc or quant_gate_rc or sampling_gate_rc
            or chunked_gate_rc or slo_gate_rc or disagg_gate_rc
            or frontdoor_gate_rc or crash_gate_rc):
        sys.exit(3)


if __name__ == "__main__":
    main()
