"""Decode/serving benchmark with roofline accounting and spread reporting.

The training side earned its numbers with ranges across sessions
(BASELINE.md); this gives the serving side the same discipline (round-5
verdict items 1 and 6):

* every timing is the MEDIAN over ``--reps`` repeat calls (plus min/max),
  with the host-side fence cost measured separately and reported — a
  single-shot decode number on this 1-core host is unfalsifiable noise;
* every row carries its bytes/step roofline: the parameter stream (decode
  params are stored in the model's compute dtype — ``Trainer.
  _decode_params``) plus the K/V cache stream, over the chip's HBM
  bandwidth.  ``roofline_x`` = measured ms / ideal ms, the factor left on
  the table.

Decode is bandwidth-bound: one step reads every block's K/V prefix and the
full parameter set, and does ~2 FLOPs per byte with them — so bytes/step
over HBM bandwidth IS the floor, and the interesting output is how far
each config sits above it.

Usage:
    python scripts/bench_decode.py [--reps 5] [--new 1024] [--hbm-gbps 819]
Prints one JSON line per config and a final summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DIM, DEPTH, HEADS, VOCAB = 512, 4, 8, 64


def build_trainer(**mk):
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    cfg = RunConfig(
        name="bench_decode", model="causal_lm",
        model_kwargs={"dim": DIM, "depth": DEPTH, "heads": HEADS,
                      "attn": "flash", **mk},
        dataset="retrieval", dataset_kwargs={"vocab": VOCAB, "seq_len": 128},
        n_train=256, n_test=128, batch_size=64, epochs=1, quiet=True,
    )
    return Trainer(cfg)


def measure_fence_s() -> float:
    """Median cost of the timing fence itself (device_get of a ready
    scalar) so per-call timings can be read net of it."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros(())
    jax.device_get(x)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.device_get(x)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def roofline_bytes(trainer, batch: int, kv_span: int, hkv: int):
    """(param_bytes, cache_bytes) one decode step streams from HBM.

    Params: the decode copy's actual leaves (compute dtype after round 5).
    Cache: every block reads K and V over the attended span — max_len for
    full attention, the W-span for windowed decode; int8 caches stream 1
    byte/element plus the per-(position, head) f32 scales.  Writes (one
    position per block) and S=1 activations are noise and not counted.
    """
    import jax

    params = trainer._decode_params()
    pbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    head_dim = DIM // HEADS
    if trainer.config.model_kwargs.get("kv_cache_dtype") == "int8":
        per_elem = 1
        scales = DEPTH * 2 * batch * kv_span * hkv * 4
    else:
        per_elem, scales = 2, 0  # bf16
    cache_bytes = DEPTH * 2 * batch * kv_span * hkv * head_dim * per_elem + scales
    return pbytes, cache_bytes


def time_config(trainer, batch: int, prompt_len: int, max_new: int,
                max_len: int, reps: int, fence_s: float, hbm_bps: float,
                label: str, kv_span: int | None = None,
                hkv: int | None = None, **gen_kw):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, VOCAB - 1, size=(batch, prompt_len)), jnp.int32)
    out = trainer.generate(prompt, max_new=max_new, max_len=max_len, **gen_kw)
    jax.device_get(jnp.sum(out))  # warmup: compile + params placement
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = trainer.generate(prompt, max_new=max_new, max_len=max_len,
                               **gen_kw)
        jax.device_get(jnp.sum(out))
        ts.append(time.perf_counter() - t0)
    med = statistics.median(ts)
    net = max(med - fence_s, 1e-9)  # decode time net of the fence transfer
    pbytes, cbytes = roofline_bytes(trainer, batch, kv_span or max_len,
                                    hkv if hkv is not None else HEADS)
    ideal_ms = (pbytes + cbytes) / hbm_bps * 1e3
    ms_per_step = net / max_new * 1e3
    # GQA-aware analytic step FLOPs (utils/flops.decode_step_flops: kv
    # projection + cache attention at the GROUPED width) over the full
    # attended span — an upper bound per step (the cache fills as the
    # episode runs), consistent with roofline_bytes' span convention
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import (
        decode_step_flops, mfu)
    # cp=1 spelled out: this bench decodes on a single chip; the cp>1
    # per-chip variant (sequence-sharded KV) is bench_cp_serving's job
    step_flops = decode_step_flops(
        batch, kv_span or max_len, DIM, HEADS, DIM // HEADS,
        heads_kv=hkv, depth=DEPTH, vocab=VOCAB, cp=1)
    step_mfu = mfu(step_flops / (net / max_new))
    row = {
        "config": label, "batch": batch, "prompt_len": prompt_len,
        "max_new": max_new, "max_len": max_len,
        "median_s": round(med, 4), "min_s": round(min(ts), 4),
        "max_s": round(max(ts), 4), "reps": reps,
        "fence_s": round(fence_s, 4),
        "tokens_per_sec": round(batch * max_new / net, 1),
        "ms_per_step": round(ms_per_step, 4),
        "param_mb_per_step": round(pbytes / 1e6, 2),
        "cache_mb_per_step": round(cbytes / 1e6, 2),
        "ideal_ms_per_step": round(ideal_ms, 4),
        "roofline_x": round(ms_per_step / ideal_ms, 2),
        "model_gflops_per_step": round(step_flops / 1e9, 4),
        "mfu": round(step_mfu, 4) if step_mfu is not None else None,
    }
    print(json.dumps(row), flush=True)
    return row


# ----------------------------------------------------------------------
# quant leg (ISSUE 12): weight-only int8 parity gate + d512 bytes model

QUANT_AGREE_FLOOR = 0.9   # greedy token agreement vs full precision
QUANT_DRIFT_BOUND = 0.05  # max |logit drift| / max |logit|, plain forward

QUANT_CONFIGS = [
    ("base", {}),
    ("gqa_window", {"heads_kv": 2, "window": 8}),
    ("tied", {"tie_embeddings": True}),
]

QUANT_PROMPTS = [[1, 2, 3, 1, 2, 3, 1, 2], [4, 5, 4, 5, 4, 5],
                 [6, 7, 8, 9], [2, 4, 2, 4, 2, 4]]


def _quant_serve(model, params, max_len, **ekw):
    from distributed_tensorflow_ibm_mnist_tpu.serving import (
        FIFOScheduler, InferenceEngine)

    eng = InferenceEngine(
        model, params, slots=2, max_len=max_len,
        scheduler=FIFOScheduler(max_len=max_len, buckets=(16,),
                                max_queue=len(QUANT_PROMPTS)),
        **ekw)
    reqs = [eng.submit(p, max_new=6) for p in QUANT_PROMPTS]
    eng.run()
    outs = [list(r.generated) for r in reqs]
    eng.close()
    return outs


def quant_parity_gate() -> int:
    """Greedy-parity gate: every zoo LM config x {dense, paged} x
    decode_ahead {1, 8} x {plain, speculative}, quant engine vs the
    full-precision reference, on BRIEFLY-FIT weights (random init leaves
    near-argmax ties everywhere, which makes greedy agreement
    unfalsifiable noise; a couple of epochs sharpens the logits so the
    floor means something).  One JSON row per cell; returns the breach
    count (caller exits 4 on any).  Paged and speculative cells are
    skipped for windowed configs (the engine rejects both compositions
    with window > 0)."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.models.quant import (
        quantize_params_int8)
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    breaches = 0
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 16, size=(2, 16)), jnp.int32)
    for name, mk in QUANT_CONFIGS:
        cfg = RunConfig(
            name=f"quant_{name}", model="causal_lm",
            model_kwargs={"dim": 32, "depth": 2, "heads": 4, **mk},
            dataset="retrieval", dataset_kwargs={"vocab": 32, "seq_len": 16},
            n_train=64, n_test=16, batch_size=16, epochs=2, quiet=True,
            eval_batch_size=16,
        )
        t = Trainer(cfg)
        t.fit()
        model, params = t.model, t._decode_params()
        ref_logits = model.apply({"params": params}, tokens)
        q_logits = model.clone(quant="int8").apply(
            {"params": quantize_params_int8(params)}, tokens)
        drift = (float(jnp.max(jnp.abs(ref_logits - q_logits)))
                 / max(float(jnp.max(jnp.abs(ref_logits))), 1e-9))
        ref = _quant_serve(model, params, 32)
        total = sum(len(t_) for t_ in ref)
        # windowed configs serve dense/plain only (the engine rejects
        # paged and speculative compositions with window > 0)
        windowed = bool(mk.get("window", 0))
        for paged in ((False,) if windowed else (False, True)):
            for k in (1, 8):
                for spec in ((False,) if windowed else (False, True)):
                    ekw = {"quant": "int8", "decode_ahead": k}
                    if paged:
                        ekw["kv_page_size"] = 8
                    if spec:
                        ekw.update(speculative="ngram", draft_len=3)
                    got = _quant_serve(model, params, 32, **ekw)
                    agree = sum(a == b for rt, gt in zip(ref, got)
                                for a, b in zip(rt, gt)) / total
                    ok = agree >= QUANT_AGREE_FLOOR and drift < QUANT_DRIFT_BOUND
                    breaches += not ok
                    print(json.dumps({
                        "quant_parity": name,
                        "layout": "paged" if paged else "dense",
                        "decode_ahead": k, "speculative": spec,
                        "agreement": round(agree, 4),
                        "rel_logit_drift": round(drift, 4), "ok": ok,
                    }), flush=True)
    return breaches


def quant_perf_leg(reps: int, hbm_bps: float):
    """d512 serving wave, full precision vs quant, with the bytes-moved
    model.  On emulated CPU the honest claim is the WEIGHT-STREAM bytes
    ratio (the thing a bandwidth-bound chip converts into step time);
    measured wall time is reported but launch-bound here."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.models import get_model
    from distributed_tensorflow_ibm_mnist_tpu.models.quant import (
        quantize_params_int8, weight_stream_bytes)

    model = get_model("causal_lm", num_classes=VOCAB, dim=DIM, depth=DEPTH,
                      heads=HEADS, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    fbytes = weight_stream_bytes(params)
    qbytes = weight_stream_bytes(quantize_params_int8(params))
    out = {}
    for label, ekw in (("f32", {}), ("int8", {"quant": "int8"})):
        _quant_serve(model, params, 32, **ekw)  # warmup: compile family
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            _quant_serve(model, params, 32, **ekw)
            ts.append(time.perf_counter() - t0)
        out[label] = statistics.median(ts)
    row = {
        "quant_perf": f"d{DIM}",
        "weight_mb_f32": round(fbytes / 1e6, 2),
        "weight_mb_int8": round(qbytes / 1e6, 2),
        "weight_bytes_ratio": round(fbytes / qbytes, 2),
        "ideal_step_ms_f32": round(fbytes / hbm_bps * 1e3, 4),
        "ideal_step_ms_int8": round(qbytes / hbm_bps * 1e3, 4),
        "median_wave_s_f32": round(out["f32"], 4),
        "median_wave_s_int8": round(out["int8"], 4),
        "note": "emulated CPU: wall time is launch-bound; the weight "
                "stream ratio is the bandwidth claim",
    }
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--new", type=int, default=1024)
    ap.add_argument("--hbm-gbps", type=float, default=819.0,
                    help="HBM bandwidth (GB/s); 819 = TPU v5e")
    ap.add_argument("--skip-window", action="store_true")
    ap.add_argument("--big", action="store_true",
                    help="add a serving-scale config (dim 2048, depth 6, "
                         "~300M params) where the roofline actually binds")
    ap.add_argument("--quant-only", action="store_true",
                    help="run the int8 weight-quant leg instead: the "
                         "greedy-parity gate (exit 4 on breach) + the d512 "
                         "bytes-moved row")
    args = ap.parse_args()
    hbm = args.hbm_gbps * 1e9

    if args.quant_only:
        breaches = quant_parity_gate()
        perf = quant_perf_leg(max(args.reps - 2, 3), hbm)
        print(json.dumps({
            "metric": "quant_decode",
            "parity_breaches": breaches,
            "parity_ok": breaches == 0,
            "agree_floor": QUANT_AGREE_FLOOR,
            "drift_bound": QUANT_DRIFT_BOUND,
            **{k: v for k, v in perf.items() if k != "quant_perf"},
        }), flush=True)
        sys.exit(4 if breaches else 0)

    import jax

    fence = measure_fence_s()
    print(json.dumps({"fence_s": round(fence, 4),
                      "device": str(jax.devices()[0])}), flush=True)

    rows = []
    trainer = build_trainer()
    for b in (1, 8, 32):
        rows.append(time_config(trainer, b, 64, args.new, 64 + args.new,
                                args.reps, fence, hbm, f"mha_b{b}"))
    # ragged tax at B=8: same shapes, per-row machinery armed
    import numpy as np

    lens = np.asarray([64, 48, 32, 64, 16, 56, 40, 64], np.int32)
    rows.append(time_config(trainer, 8, 64, args.new, 64 + args.new,
                            args.reps, fence, hbm, "mha_b8_ragged",
                            prompt_lens=lens))

    gqa = build_trainer(heads_kv=2)
    rows.append(time_config(gqa, 8, 64, args.new, 64 + args.new,
                            args.reps, fence, hbm, "gqa2_b8", hkv=2))

    if not args.skip_window:
        win = build_trainer(window=1024)
        rows.append(time_config(win, 8, 64, 2048, 8192, max(args.reps - 2, 3),
                                fence, hbm, "win1024_b8_cache8192",
                                kv_span=1024 + 0))
        full = build_trainer()
        rows.append(time_config(full, 8, 64, 2048, 8192,
                                max(args.reps - 2, 3), fence, hbm,
                                "full_b8_cache8192"))
        # int8 KV cache at the same cache-dominated shape (round 5)
        i8 = build_trainer(kv_cache_dtype="int8")
        rows.append(time_config(i8, 8, 64, 2048, 8192,
                                max(args.reps - 2, 3), fence, hbm,
                                "int8_b8_cache8192"))

    if args.big:
        # serving-scale: bytes dominate, launch overhead amortizes — this
        # is the row where roofline_x approaches 1 (see the roofline note
        # in docs/PERFORMANCE.md; the dim-512 rows are launch-bound)
        global DIM, DEPTH, HEADS
        DIM, DEPTH, HEADS = 2048, 6, 16
        big = build_trainer()
        for b in (1, 8):
            rows.append(time_config(big, b, 64, 256, 320, args.reps, fence,
                                    hbm, f"big2048_b{b}"))

    print(json.dumps({"summary": {r["config"]: r["tokens_per_sec"]
                                  for r in rows}}), flush=True)


if __name__ == "__main__":
    main()
