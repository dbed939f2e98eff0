#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               # on a machine with a TPU
    python chip_smoke.py --cpu-dry-run # tiny sizes on the CPU; never a pass

Drives the two main paths once each through the entry points a user calls,
at the full width of the models the repo tracks (weights random, from a
seed; nothing outside the checkout is read, no network):

* trainer — ``get_preset("mnist_lenet_1chip")`` through ``Trainer.fit()``
  and ``measure_throughput`` as ``launch/cli.py`` does, twice in two fresh
  processes (the second must hit the persistent compile cache the first
  wrote); and the tracked LM (``causal_lm`` dim 512 / depth 4 / heads 8,
  ``attn="flash"``, S=8192, batch 8) for a few steps, so the flash forward
  and one-walk backward compile under Mosaic;
* server — ``InferenceEngine.from_trainer`` on the widest LM the repo has
  run (dim 2048 / depth 6 / heads 16, bf16): ``prewarm()``, mixed-length
  requests to completion in the paged+radix and the dense KV layout, every
  token (near-)greedy under one reference forward, tokens identical
  between the layouts and — one row per program — to ``Trainer.generate``
  (``make_generator``), zero programs after prewarm; then the same engine
  behind ``Router -> ServingDaemon -> FrontDoor`` answering one unary and
  one SSE ``POST /v1/generate`` over loopback;
* kernels — every Pallas shape on those paths (and the long-row, windowed
  and grouped-query forms) against ``vanilla_attention`` / optax, each
  proven Mosaic-compiled from its lowered module;
* with >= 4 TPU devices, the multi-chip legs: LeNet dp=4 (replicated and
  ZeRO-1 update), the LM at sp=4 ring+flash, the engine at tp=4 and at
  cp=2 x tp=2 — asserting from the arrays that all four chips hold shards.

One process owns a chip at a time, so every leg is a child process, run
strictly one after another, and this parent never imports jax.  Each leg
prints one JSON record (device, versions, compile seconds, compiled-program
count, persistent-cache hits, its checks).  The run fails — exit 1, no
result line — if any leg fails, if ``jax.devices()[0].platform != "tpu"``,
if any Pallas call was lowered through the interpreter, or if the device
kind has no row in the peak table.  On success the last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Whole-run wall budget (the contract allows 1200 s, compilation included)
# and the most one leg may take of it.
RUN_BUDGET_S = 1150.0
LEG_BUDGET_S = 600.0

# (rows, classes) the fused cross-entropy is probed at: the LeNet loss and an
# LM vocabulary width.
XENT_SHAPES = ((1024, 10), (2048, 32768))


# ----------------------------------------------------------------------
# child side: everything from here to ``run_leg`` runs in a leg's own
# process and is the only code that imports jax


class Leg:
    """One leg's process-wide set-up and its record."""

    def __init__(self, name: str, dry_run: bool):
        import jax

        from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret
        from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import (
            enable_compile_cache,
        )
        from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
            device_peak_tflops,
        )
        from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

        self.name = name
        self.dry_run = dry_run
        self.t0 = time.perf_counter()
        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        if dry_run:
            if self.device["platform"] != "cpu":
                raise RuntimeError(
                    f"--cpu-dry-run is for the CPU; jax found {self.device}")
            set_interpret(True)  # the dry run's explicit choice
        elif self.device["platform"] != "tpu":
            raise RuntimeError(
                f"no TPU found: jax.devices()[0].platform == "
                f"{self.device['platform']!r} ({self.device['count']} "
                f"device(s)); this smoke only passes on the chip "
                f"(--cpu-dry-run debugs it on the CPU at tiny sizes)")
        # unknown TPU kind raises here; None only on the dry run's CPU
        self.peak_tflops = device_peak_tflops(devices[0])
        self.cache_dir = enable_compile_cache()
        self.tracker = CompileTracker.install()
        self.checks: dict = {}

    def versions(self) -> dict:
        import jax
        import jaxlib

        try:
            import libtpu

            libtpu_version = getattr(libtpu, "__version__", "unknown")
        except ImportError:
            libtpu_version = None
        return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version}

    def record(self) -> dict:
        from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import interpret_forced

        snap = self.tracker.snapshot()
        return {
            "leg": self.name, "ok": True, "dry_run": self.dry_run,
            "device": self.device, "versions": self.versions(),
            "peak_bf16_tflops": self.peak_tflops,
            "pallas_interpreted": interpret_forced(),
            "compile_cache_dir": self.cache_dir,
            "n_compiled_programs": snap["n_compiled_programs"],
            "compile_s": snap["compile_time_s"],
            "persistent_cache_hits": snap["persistent_cache_hits"],
            "wall_s": round(time.perf_counter() - self.t0, 2),
            **self.checks,
        }


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _finite(x) -> bool:
    return x is not None and math.isfinite(float(x))


def _bytes_in_use() -> list:
    """Per-device ``bytes_in_use`` (None where the backend reports none)."""
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]


def _native_status() -> dict:
    """Build (if needed) and exercise the native data library; a failed
    build is a failed leg — the numpy path is only acceptable where it was
    chosen (DTM_DISABLE_NATIVE), not where g++ broke."""
    import numpy as np

    from distributed_tensorflow_ibm_mnist_tpu.data import native

    st = native.status()
    _require(st["path"] == "native" or os.environ.get("DTM_DISABLE_NATIVE"),
             f"native data library did not build: {st}")
    src = np.arange(64 * 7, dtype=np.uint8).reshape(64, 7)
    idx = np.asarray([5, 0, 63, 17], np.int32)
    _require(np.array_equal(native.gather(src, idx), src[idx]),
             "native.gather disagrees with numpy")
    return st


def leg_lenet(leg: Leg) -> None:
    """The flagship trainer, as launch/cli.py drives it."""
    from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import get_preset

    leg.checks["native_data"] = _native_status()
    # synthetic=True: data/loaders.py otherwise searches ~/.keras, /tmp/mnist_data
    # and /root/data — files outside the checkout
    cfg = get_preset("mnist_lenet_1chip").replace(synthetic=True, quiet=True)
    if leg.dry_run:
        cfg = cfg.replace(n_train=512, n_test=128, batch_size=64, epochs=1,
                          target_accuracy=None)
    trainer = Trainer(cfg)
    summary = trainer.fit()
    tput = trainer.measure_throughput(epochs=1 if leg.dry_run else 3)
    losses = [h["train_loss"] for h in trainer.history]
    leg.checks.update(
        best_test_accuracy=summary["best_test_accuracy"],
        target_accuracy=cfg.target_accuracy,
        epochs_run=summary["epochs_run"], final_train_loss=losses[-1],
        compile_and_first_epoch_s=tput["compile_and_first_epoch_s"],
        images_per_sec_per_chip=tput["images_per_sec_per_chip"],
        mfu=tput["mfu"], trainer_device=tput["device"])
    _require(all(_finite(x) for x in losses) and _finite(tput["last_loss"]),
             f"non-finite LeNet loss: {losses}, {tput['last_loss']}")
    if not leg.dry_run:
        _require(summary["time_to_target_s"] is not None,
                 f"LeNet missed its accuracy threshold: {summary}")
        _require(tput["mfu"] is not None, "no MFU on a chip in the peak table")


def _lm_config(leg: Leg, **over):
    """The tracked long-context LM of rounds 3-5 (dim 512, S=8192, flash)."""
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    kw = dict(
        name="smoke_lm8k", model="causal_lm",
        model_kwargs={"dim": 512, "depth": 4, "heads": 8, "attn": "flash"},
        dataset="retrieval", dataset_kwargs={"vocab": 256, "seq_len": 8192},
        n_train=64, n_test=16, batch_size=8, epochs=2, quiet=True,
        eval_batch_size=8)
    if leg.dry_run:
        kw.update(
            model_kwargs={"dim": 32, "depth": 1, "heads": 2, "attn": "flash",
                          "dtype": jnp.float32},
            dataset_kwargs={"vocab": 16, "seq_len": 64},
            n_train=16, n_test=8, batch_size=8, eval_batch_size=8)
    kw.update(over)
    return RunConfig(**kw)


def leg_lm(leg: Leg) -> None:
    """A few steps of the S=8192 flash LM: the fused forward and one-walk
    backward compile under Mosaic inside the trainer's real epoch program."""
    from distributed_tensorflow_ibm_mnist_tpu.core import Trainer

    trainer = Trainer(_lm_config(leg))
    summary = trainer.fit()
    losses = [h["train_loss"] for h in trainer.history]
    leg.checks.update(steps=trainer.steps_per_epoch * summary["epochs_run"],
                      train_losses=losses,
                      tokens_per_sec_per_chip=summary.get("tokens_per_sec_per_chip"),
                      mfu=summary.get("mfu"))
    _require(all(_finite(x) for x in losses), f"non-finite LM loss: {losses}")


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.maximum(jnp.max(jnp.abs(want)), 1.0))


def _mosaic_compiled(jitted, *args) -> bool:
    """Whether the lowered module carries a Mosaic custom call."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def _flash_probe(leg: Leg, name: str, b, s, h, d, dtype, *, causal=True,
                 window=0, heads_kv=None, backward=True) -> dict:
    """flash_attention vs vanilla_attention on one shape, forward and
    (optionally) backward; errors relative to the reference's magnitude.
    f32 keeps tests/test_tpu_hardware.py's 5e-3; bf16 gets 2e-2 (its
    epsilon is 7.8e-3)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import flash_attention
    from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
        vanilla_attention,
    )

    rng = np.random.default_rng(0)
    hkv = heads_kv or h
    q = jnp.asarray(rng.normal(0, 0.5, (b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(0, 0.5, (b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(0, 0.5, (b, s, hkv, d)), dtype)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window)

    def dense(q, k, v):
        return vanilla_attention(q, k, v, causal=causal, window=window)

    def loss(fn):
        return lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum()

    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    fwd = jax.jit(flash)
    out = {"name": name, "tol": tol,
           "mosaic": _mosaic_compiled(fwd, q, k, v),
           "fwd_err": _rel_err(fwd(q, k, v), jax.jit(dense)(q, k, v))}
    errs = [out["fwd_err"]]
    if backward:
        gfn = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))
        out["mosaic"] = out["mosaic"] and _mosaic_compiled(gfn, q, k, v)
        got = gfn(q, k, v)
        want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
        out["bwd_err"] = max(_rel_err(a, b_) for a, b_ in zip(got, want))
        errs.append(out["bwd_err"])
    _require(all(_finite(e) and e < tol for e in errs),
             f"flash probe {name} out of tolerance: {out}")
    _require(out["mosaic"] or leg.dry_run,
             f"flash probe {name} was not compiled by Mosaic")
    return out


def _paged_probe(leg: Leg, name: str, dtype, hkv: int, group: int) -> dict:
    """The paged decode kernel vs the gather + masked softmax it replaces, on
    one ragged batch over a pool with a NaN trash page.  What only the chip
    can show: which half of a 32-bit word holds which KV head."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_ibm_mnist_tpu.models.transformer import _attend_cached
    from distributed_tensorflow_ibm_mnist_tpu.ops.paged_attention import (
        paged_decode_attention,
    )

    ps, n_row, d = (8, 20, 128) if leg.dry_run else (64, 64, 128)
    max_len = ps * n_row
    lens = [1, ps, ps + 1, max_len // 3, max_len, 2, 9 * ps + 5]
    pages = [-(-n // ps) for n in lens]
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 0.5, (len(lens), hkv * group, d)), dtype)
    pool_k = jnp.asarray(rng.normal(0, 0.5, (1 + sum(pages), ps, hkv, d)), dtype)
    pool_v = jnp.asarray(rng.normal(0, 0.5, (1 + sum(pages), ps, hkv, d)), dtype)
    bt = np.zeros((len(lens), n_row), np.int32)
    ids = iter(rng.permutation(np.arange(1, 1 + sum(pages))))
    for r, n in enumerate(pages):
        bt[r, :n] = [next(ids) for _ in range(n)]
    bt, lengths = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)

    def gather(q, pool_k, pool_v):
        kc = pool_k[bt].reshape(len(lens), max_len, hkv, d)
        vc = pool_v[bt].reshape(len(lens), max_len, hkv, d)
        mask = jnp.arange(max_len)[None, None, :] < lengths[:, None, None]
        return _attend_cached(q[:, None], kc, vc, None, None, mask, dtype)[:, 0]

    kernel = jax.jit(lambda q, k, v: paged_decode_attention(q, k, v, bt, lengths))
    want = jax.jit(gather)(q, pool_k, pool_v)
    poisoned = pool_k.at[0].set(jnp.nan), pool_v.at[0].set(jnp.inf)
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    out = {"name": name, "tol": tol,
           "mosaic": _mosaic_compiled(kernel, q, *poisoned),
           "fwd_err": _rel_err(kernel(q, *poisoned), want)}
    _require(_finite(out["fwd_err"]) and out["fwd_err"] < tol,
             f"paged probe {name} out of tolerance: {out}")
    _require(out["mosaic"] or leg.dry_run,
             f"paged probe {name} was not compiled by Mosaic")
    return out


def _xent_probe(leg: Leg, n: int, c: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_ibm_mnist_tpu.ops.xent import softmax_xent_mean

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 1, (n, c)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, c, n), jnp.int32)

    def ref(x):
        return optax.softmax_cross_entropy_with_integer_labels(x, labels).mean()

    fn = jax.jit(jax.value_and_grad(softmax_xent_mean))
    loss, grad = fn(logits, labels)
    rloss, rgrad = jax.jit(jax.value_and_grad(ref))(logits)
    out = {"name": f"xent_{n}x{c}", "tol": 1e-4,
           "mosaic": _mosaic_compiled(fn, logits, labels),
           "loss_err": abs(float(loss) - float(rloss)),
           "grad_err": float(jnp.max(jnp.abs(grad - rgrad)))}
    _require(out["loss_err"] < 1e-4 and out["grad_err"] < 1e-4,
             f"xent probe out of tolerance: {out}")
    _require(out["mosaic"] or leg.dry_run,
             f"xent probe {out['name']} was not compiled by Mosaic")
    return out


def leg_kernels(leg: Leg) -> None:
    """Every Pallas shape on the smoke's paths, checked against the dense
    reference and proven Mosaic-compiled."""
    import jax.numpy as jnp

    bf16, f32 = jnp.bfloat16, jnp.float32
    probes = []
    # the engine's bucketed prefill: B=1, S in the default buckets, at the
    # dim-2048 / 16-head model's head_dim
    for s in (16, 32, 64, 128):
        for dtype in (bf16, f32):
            probes.append(_flash_probe(
                leg, f"prefill_S{s}_{jnp.dtype(dtype).name}", 1, s, 16, 128,
                dtype, backward=False))
    long_s, longer_s = (64, 128) if leg.dry_run else (8192, 16384)
    # the tracked training shape: the fused one-walk backward
    probes.append(_flash_probe(leg, f"fused_S{long_s}_D64", 1, long_s, 2, 64, bf16))
    probes.append(_flash_probe(leg, f"fused_S{long_s}_D128", 1, long_s, 2, 128, bf16))
    # past the fused gate: the grouped backward and its VMEM budget
    probes.append(_flash_probe(leg, f"grouped_S{longer_s}_D64", 1, longer_s, 1, 64, bf16))
    short_s = 64 if leg.dry_run else 2048
    probes.append(_flash_probe(leg, f"window_S{short_s}", 1, short_s, 2, 64,
                               bf16, window=short_s // 8))
    probes.append(_flash_probe(leg, f"gqa_S{short_s}_h8_kv2", 1, short_s, 8,
                               64, bf16, heads_kv=2))
    # the serving window's paged decode attention: the benchmark's GQA
    # group of 12 on a bf16 pair of KV heads, and the strided f32 split
    probes.append(_paged_probe(leg, "paged_bf16_kv2_g12", bf16, 2, 12))
    probes.append(_paged_probe(leg, "paged_bf16_kv8_g2", bf16, 8, 2))
    probes.append(_paged_probe(leg, "paged_f32_kv2_g4", f32, 2, 4))
    for n, c in XENT_SHAPES:
        if leg.dry_run:
            n, c = min(n, 64), min(c, 512)
        probes.append(_xent_probe(leg, n, c))
    leg.checks["probes"] = probes


def _engine_trainer(leg: Leg):
    """An untrained (seeded random-init) run of the widest LM the repo has
    served: what InferenceEngine.from_trainer and Trainer.generate take."""
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    mk = {"dim": 2048, "depth": 6, "heads": 16, "attn": "flash"}
    vocab = 256
    if leg.dry_run:
        mk = {"dim": 64, "depth": 2, "heads": 4, "attn": "flash",
              "dtype": jnp.float32}
        vocab = 32
    return Trainer(RunConfig(
        name="smoke_serve", model="causal_lm", model_kwargs=mk,
        dataset="retrieval", dataset_kwargs={"vocab": vocab, "seq_len": 128},
        n_train=16, n_test=8, batch_size=8, epochs=1, quiet=True,
        eval_batch_size=8)), vocab


MAX_LEN, PAGE = 160, 16


def _requests(vocab: int):
    """Mixed-length prompts over every default bucket with mixed budgets.
    The last one is a second turn: it repeats an earlier prompt's first
    two pages and is submitted after the rest have been served, so the
    paged engine must find those pages in its radix trie."""
    import numpy as np

    rng = np.random.default_rng(7)
    lens = (5, 12, 17, 30, 40, 64, 100, 120, 50)
    budgets = (8, 16, 4, 12, 24, 6, 16, 10, 8)
    prompts = [rng.integers(1, vocab - 1, size=n).astype(np.int32) for n in lens]
    prompts[-1][:2 * PAGE] = prompts[5][:2 * PAGE]
    return prompts, budgets


def _greedy_gap(model, params, prompts, streams) -> float:
    """How far any generated token sits below the argmax of ONE reference
    program: the model's plain forward over prompt + generated tokens
    (teacher-forced, every request in one batch).  0 for a stream that is
    exactly that program's greedy decode; a few hundredths of a logit where
    a bf16 program of another shape took the other side of a near-tie (both
    continuations are then greedy for their own prefix); whole logits for a
    wrong cache row, position or mask."""
    import jax
    import numpy as np

    rows = np.zeros((len(prompts), MAX_LEN), np.int32)
    for i, (p, g) in enumerate(zip(prompts, streams)):
        rows[i, :p.size + len(g)] = np.concatenate([p, np.asarray(g, np.int32)])
    logits = np.asarray(jax.jit(
        lambda prm, x: model.apply({"params": prm}, x))(params, rows), np.float32)
    worst = 0.0
    for i, (p, g) in enumerate(zip(prompts, streams)):
        at = logits[i, p.size - 1:p.size - 1 + len(g)]  # row t predicts token t+1
        worst = max(worst, float(np.max(at.max(-1) - at[np.arange(len(g)), g])))
    return worst


# the most a greedy token may trail the reference forward's argmax: bf16
# logits of this model carry ~0.01-0.03 of rounding, its top-2 gaps are ~0.3
GREEDY_TOL = 0.1


def _serve(engine, prompts, budgets, want, reference=None) -> tuple[list, dict]:
    """prewarm, serve, and gate what every engine must show: each request
    done with exactly its budget, nothing compiled AFTER prewarm, every
    token (near-)greedy under ``reference`` — a single-chip ``(model,
    params)``, the engine's own unless it is sharded.  ``want`` (the
    generator's tokens) is only counted against: see _reference_tokens."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

    warm = engine.prewarm()
    before = engine._compile.snapshot()
    reqs = [engine.submit(p, max_new=b)
            for p, b in zip(prompts[:-1], budgets[:-1])]
    engine.run()
    reqs.append(engine.submit(prompts[-1], max_new=budgets[-1]))  # 2nd turn
    engine.run()
    after = CompileTracker.delta(engine._compile.snapshot(), before)
    for r, b in zip(reqs, budgets):
        _require(r.status == "done" and len(r.generated) == b,
                 f"request {r.id}: status {r.status}, "
                 f"{len(r.generated)}/{b} tokens, error {r.error!r}")
    # (a program served by a warm persistent cache counts too: CompileTracker)
    post = after["n_compiled_programs"]
    _require(post == 0, f"programs after prewarm(): {after}")
    tokens = [list(map(int, r.generated)) for r in reqs]
    gap = _greedy_gap(*(reference or (engine.model, engine.params)),
                      prompts, tokens)
    _require(gap < GREEDY_TOL,
             f"a token trails the reference argmax by {gap:.3f} logits")
    return tokens, {
        "prewarm_programs": warm["programs"],
        "prewarm_compile_s": warm["compile_s"],
        "post_prewarm_programs": post, "greedy_gap": gap,
        "requests_equal_generator": sum(a == b for a, b in zip(tokens, want))}


BUCKETS = (16, 32, 64, 128)  # the engine's default prefill buckets


def _reference_tokens(trainer, prompts, budgets) -> list:
    """Greedy tokens from make_generator (through Trainer.generate), one
    request at a time, its prompt right-padded to the engine's bucket.

    One row per program on purpose.  In bf16 on the v5e the decode matmuls
    round differently at batch 1 than at batch >= 4 (measured, PR 21: of
    this stream's 96 tokens, request 3's 11th flips between the two on a
    near-tie of a random-init model; every batch-1 program — the generator
    solo, padded or not, and a one-slot engine — agrees token for token,
    and so do the 4- and 8-slot engines among themselves).  So identity
    with the reference is checked where the arithmetic is the same."""
    import numpy as np

    out = []
    for p, b in zip(prompts, budgets):
        row = np.zeros((1, next(w for w in BUCKETS if w >= p.size)), np.int32)
        row[0, :p.size] = p
        tokens = np.asarray(trainer.generate(
            row, max_new=max(budgets), max_len=MAX_LEN,
            prompt_lens=np.asarray([p.size], np.int32)))
        out.append(list(map(int, tokens[0, p.size:p.size + b])))
    return out


def leg_engine(leg: Leg) -> None:
    """The serving path: engine in both KV layouts vs make_generator, then
    the same engine behind Router -> ServingDaemon -> FrontDoor."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        FrontDoor,
        FrontDoorClient,
        InferenceEngine,
        Router,
        ServingDaemon,
    )

    trainer, vocab = _engine_trainer(leg)
    prompts, budgets = _requests(vocab)
    want = _reference_tokens(trainer, prompts, budgets)

    def make(slots=4, **kw):
        return InferenceEngine.from_trainer(
            trainer, slots=slots, max_len=MAX_LEN, **kw)

    def differing(a, b):
        return [i for i, (x, y) in enumerate(zip(a, b)) if x != y]

    layouts, tokens = {}, {}
    for name, kw in (("paged", {"kv_page_size": PAGE}), ("dense", {}),
                     ("dense_one_slot", {"slots": 1})):
        with make(**kw) as engine:
            tokens[name], layouts[name] = _serve(engine, prompts, budgets, want)
            layouts[name]["radix_hits"] = engine.stats.summary()["radix_hits"]
    # Token IDENTITY is gated where both sides run the same arithmetic:
    # the first-turn requests in the two KV layouts (same programs but for
    # the page gather), and a one-slot engine against make_generator (one
    # row per program).  The second turn is where the layouts differ by
    # design — paged prefills only its suffix, attending to the trie's
    # pages through the decode-path attention, dense re-prefills the whole
    # prompt through the flash kernel — and in bf16 that pair parted on a
    # near-tie on the v5e (PR 21); greedy_gap above is its gate.
    first = slice(0, len(prompts) - 1)
    _require(tokens["paged"][first] == tokens["dense"][first],
             f"paged != dense on first-turn requests "
             f"{differing(tokens['paged'][first], tokens['dense'][first])}")
    _require(layouts["paged"]["radix_hits"] > 0,
             "the second turn's shared pages never hit the radix trie")
    _require(tokens["dense_one_slot"] == want,
             f"engine != make_generator on requests "
             f"{differing(tokens['dense_one_slot'], want)}")
    layouts["paged"]["second_turn_equals_dense"] = (
        tokens["paged"][-1] == tokens["dense"][-1])
    leg.checks["layouts"] = layouts
    leg.checks["param_count"] = trainer.state.param_count()
    want = tokens["paged"]  # the front door serves through the 4-slot engine

    router = Router(lambda tid: make(kv_page_size=PAGE, trace_tid=tid), 1)
    router.prewarm()
    daemon = ServingDaemon(router, max_queue=16, liveness_timeout_s=120.0).start()
    door = FrontDoor(daemon).start_in_thread()
    try:
        client = FrontDoorClient("127.0.0.1", door.port, timeout=120.0)
        body = client.generate(prompts[2], budgets[2])
        _require(client.last_status == 200 and body.get("tokens") == want[2],
                 f"unary POST /v1/generate: HTTP {client.last_status} {body}")
        streamed = list(client.stream(prompts[3], budgets[3]))
        _require(streamed == want[3] and
                 (client.last_terminal or {}).get("status") == "done",
                 f"SSE POST /v1/generate: {streamed} / {client.last_terminal}")
        leg.checks["frontdoor"] = {"unary": "ok", "sse": "ok",
                                   "healthz": client.healthz().get("status")}
    finally:
        door.stop()
        daemon.drain(timeout=60.0)
        daemon.close()
    leg.checks["bytes_in_use"] = _bytes_in_use()


def _span(tree, n_devices: int, what: str) -> dict:
    """Assert from the arrays themselves that ``tree`` lives on
    ``n_devices`` chips: every leaf has addressable shards on that many
    distinct devices, and where its sharding splits it each chip holds
    exactly its piece, not the whole."""
    import jax

    sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        where = f"{what}{jax.tree_util.keystr(path)}"
        devs = {s.device.id for s in leaf.addressable_shards}
        _require(len(devs) == n_devices,
                 f"{where} sits on {len(devs)} device(s), want {n_devices}")
        piece = leaf.sharding.shard_shape(leaf.shape)
        _require(all(s.data.shape == piece for s in leaf.addressable_shards),
                 f"{where}: shards are not the {piece} pieces its sharding names")
        sharded += piece != leaf.shape
    return {"devices": n_devices, "sharded_leaves": sharded}


def _chips_busy(n: int) -> list:
    """bytes_in_use per chip; every one of the first ``n`` must be
    non-trivial (the backend's own idle footprint is ~27 KB)."""
    used = _bytes_in_use()
    if used[0] is not None:
        _require(all(u > (1 << 20) for u in used[:n]),
                 f"a chip holds no work: bytes_in_use {used}")
    return used


def leg_multichip(leg: Leg) -> None:
    """Four chips: dp, ZeRO-1, sp ring+flash, tp and cp x tp — and where
    four tp=1 replicas land."""
    import jax

    from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.serving import InferenceEngine, Router
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import get_preset

    out = leg.checks
    lenet = get_preset("mnist_lenet_1chip").replace(
        synthetic=True, quiet=True, dp=4, batch_size=512, epochs=2,
        n_train=16384, n_test=4096, target_accuracy=None)
    if leg.dry_run:
        lenet = lenet.replace(n_train=512, n_test=128, batch_size=64, epochs=1)
    for name, cfg in (("lenet_dp4", lenet),
                      ("lenet_dp4_sharded_update",
                       lenet.replace(sharded_update=True))):
        trainer = Trainer(cfg)
        summary = trainer.fit()
        _require(_finite(trainer.history[-1]["train_loss"]), f"{name}: loss")
        out[name] = {
            "params": _span(trainer.state.params, 4, f"{name} params"),
            "opt_state": _span(trainer.state.opt_state, 4, f"{name} opt"),
            "train_images": _span(trainer.train_images, 4, f"{name} data"),
            "best_test_accuracy": summary["best_test_accuracy"],
            "bytes_in_use": _chips_busy(4)}
        trainer.close()
        del trainer
    _require(out["lenet_dp4_sharded_update"]["opt_state"]["sharded_leaves"] > 0,
             "sharded_update left every optimizer leaf whole on each chip")

    trainer = Trainer(_lm_config(leg, name="smoke_lm_sp4", sp=4, sp_impl="ring",
                                 epochs=1))
    trainer.fit()
    _require(_finite(trainer.history[-1]["train_loss"]), "lm sp=4: loss")
    out["lm_sp4_ring_flash"] = {
        "params": _span(trainer.state.params, 4, "lm sp4 params"),
        "train_images": _span(trainer.train_images, 4, "lm sp4 data"),
        "train_loss": trainer.history[-1]["train_loss"],
        "bytes_in_use": _chips_busy(4)}
    trainer.close()
    del trainer

    trainer, vocab = _engine_trainer(leg)
    prompts, budgets = _requests(vocab)
    want = _reference_tokens(trainer, prompts, budgets)
    with InferenceEngine.from_trainer(trainer, slots=1, max_len=MAX_LEN) as one_chip:
        reference = one_chip.model, one_chip.params
    for name, kw in (("engine_tp4", {"tp": 4}),
                     ("engine_cp2_tp2", {"cp": 2, "tp": 2})):
        with InferenceEngine.from_trainer(
                trainer, slots=4, max_len=MAX_LEN, kv_page_size=PAGE,
                **kw) as engine:
            # sharded matmuls reduce in another order than one chip does, so
            # bf16 greedy tokens may part ways on a near-tie: the gate is
            # the single-chip reference forward (the CPU tests pin identity)
            _, census = _serve(engine, prompts, budgets, want, reference)
            census["params"] = _span(engine.params, 4, f"{name} params")
            census["kv"] = _span(engine.cache, 4, f"{name} kv")
            census["bytes_in_use"] = _chips_busy(4)
        _require(census["params"]["sharded_leaves"] > 0
                 and census["kv"]["sharded_leaves"] > 0,
                 f"{name}: nothing is sharded: {census}")
        out[name] = census

    # where do four tp=1 replicas behind a Router live?  (Replica takes no
    # device: ROADMAP B6)
    router = Router(
        lambda tid: InferenceEngine.from_trainer(
            trainer, slots=2, max_len=MAX_LEN, kv_page_size=PAGE,
            trace_tid=tid), 4)
    out["four_tp1_replicas_param_devices"] = [
        sorted({d.id for leaf in jax.tree.leaves(rep.engine.params)
                for d in leaf.devices()})
        for rep in router.replicas]
    router.close()


LEGS = {
    "lenet": leg_lenet,
    "lm": leg_lm,
    "kernels": leg_kernels,
    "engine": leg_engine,
    "multichip": leg_multichip,
}


def run_leg(name: str, dry_run: bool = False) -> int:
    """A leg's whole process: set up, run, print ONE JSON record line.
    Exit code 0 only if the leg passed."""
    import traceback

    leg = None
    try:
        leg = Leg(name, dry_run)
        LEGS[name](leg)
        record = leg.record()
        _require(dry_run or not record["pallas_interpreted"],
                 "a Pallas kernel was lowered through the interpreter")
    except Exception as e:  # the leg's boundary: report, then fail the process
        traceback.print_exc()
        record = {"leg": name, "ok": False, "dry_run": dry_run,
                  "error": f"{type(e).__name__}: {e}"[:2000]}
        if leg is not None:  # what it had established before it failed
            record.update(device=leg.device, **leg.checks)
    print(json.dumps(record), flush=True)
    return 0 if record["ok"] else 1


# ----------------------------------------------------------------------
# parent side: no jax here


def _spawn(name: str, dry_run: bool, timeout_s: float) -> dict:
    """Run one leg in a fresh process and return its record."""
    env = dict(os.environ)
    if dry_run:
        env["JAX_PLATFORMS"] = "cpu"
    code = (f"import sys, chip_smoke; "
            f"sys.exit(chip_smoke.run_leg({name!r}, dry_run={dry_run!r}))")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, env=env,
            stdout=subprocess.PIPE, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"leg": name, "ok": False,
                "error": f"timed out after {timeout_s:.0f}s (killed)"}
    record = None
    for line in proc.stdout.splitlines():
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and parsed.get("leg") == name:
            record = parsed
    if record is None:
        record = {"leg": name, "ok": False,
                  "error": f"no record (exit {proc.returncode})"}
    if proc.returncode != 0:
        record["ok"] = False
    record["process_s"] = round(time.perf_counter() - t0, 1)
    return record


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-dry-run", action="store_true",
        help="debug the smoke's control flow on the CPU at tiny sizes with "
             "interpreted kernels; prints \"dry_run\": true and never the "
             "pass line")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run
    t0 = time.perf_counter()
    deadline = t0 + RUN_BUDGET_S
    records: list[dict] = []

    def run(name: str, label: str | None = None) -> dict:
        left = deadline - time.perf_counter()
        if left <= 0:
            rec = {"leg": name, "ok": False, "error": "run budget spent"}
        else:
            rec = _spawn(name, dry, min(LEG_BUDGET_S, left))
        rec["leg"] = label or name
        records.append(rec)
        # a failed leg's record goes to stderr: stdout never carries a
        # result for a run that did not pass
        print(json.dumps(rec), flush=True,
              file=sys.stdout if rec["ok"] else sys.stderr)
        return rec

    first = run("lenet")
    if "device" not in first:
        # no device, no package, no jax: nothing further can run
        print(f"chip_smoke: FAILED at the first leg: {first.get('error')}",
              file=sys.stderr)
        return 1
    again = run("lenet", "lenet_second_process")
    if again["ok"] and not dry and not again["persistent_cache_hits"]:
        again["ok"] = False
        again["error"] = ("a second fresh process found nothing in the "
                          f"persistent cache at {again['compile_cache_dir']}")
    for name in ("lm", "kernels", "engine"):
        run(name)
    n = first["device"]["count"]
    if n >= 4:
        run("multichip")
        multichip = "ok" if records[-1]["ok"] else "FAILED"
    else:
        multichip = f"not run: {n} device(s)"

    failed = [r["leg"] for r in records if not r["ok"]]
    summary = {
        "summary": "chip_smoke", "dry_run": dry, "legs_ok": not failed,
        "failed": failed, "multichip": multichip,
        "wall_s": round(time.perf_counter() - t0, 1),
        "device": first["device"],
        "compile_s": {r["leg"]: r.get("compile_s") for r in records},
        "n_compiled_programs": {r["leg"]: r.get("n_compiled_programs")
                                for r in records},
        "persistent_cache_hits": {r["leg"]: r.get("persistent_cache_hits")
                                  for r in records},
    }
    if failed:
        print(json.dumps(summary), file=sys.stderr, flush=True)
        for r in records:
            if not r["ok"]:
                print(f"chip_smoke: leg {r['leg']} FAILED: {r.get('error')}",
                      file=sys.stderr)
        return 1
    print(json.dumps(summary), flush=True)
    if not dry:
        # the pass line: only ever printed for a run on the chip
        print(json.dumps({"ok": True, "device": first["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
