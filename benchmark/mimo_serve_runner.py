"""The MiMo-V2-Flash serving cell: one chip's share of a 16-way
expert-parallel deployment through ``InferenceEngine`` with ``prewarm()``,
chunked prefill, under the closed-loop client of ``serve_runner`` with
mixed prompt lengths.

Set-up: bf16 weights made on the device from ``--seed``; the engine (page
pool for the global layers, one ring a slot for each window layer, the held
experts' banks) and its ``prewarm()`` (two programs: the decode window and
the one extend chunk); the correctness check at the timed widths on the
timed path; the warm-in that admits every client's first request.  Then the
window.

The check (it decides ``correct``): two requests, one short and one longer
than four chunks, chunk-prefilled and decoded through the caches, each
against ONE plain-reference forward (``reference_mimo``) of prompt + answer.
Beside the logits it reads what the new mechanisms PRODUCE out of the
engine's cache: the experts each decode step chose, each window layer's ring
at the end, the device's own count of the pairs its held experts computed.
The limits, each between the engine's reading and the control's (the
reference with float8 weights, a bf16 router, no sink and no correction
bias), are in the configuration file's ``check`` and ``PERF.md``;
``benchmark/tests/control_mimo.py`` runs the control, which has to fail.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import harness, reference_mimo, traffic as traffic_gen
from benchmark.sala_serve_runner import LongDocLoop
from benchmark.serve_runner import drive, make_weights

# standard deviations of the leaves ``make_weights`` cannot size by their
# first axis: a bank's fan-in is its second axis; the sinks and the
# correction bias are drawn large enough that dropping either moves the
# check's readings out of their limits (the sink's logit competes with 128
# scores of unit scale; the bias with the ~0.1-wide band of sigmoid scores
# the top 8 of 256 lie in: at 0.1 it ALONE picked the experts, 9 of 96 held
# ones taking all the load on the chip, at 0.02 the token still does)
LEAF_STD = {"sink": 2.0, "router_bias": 0.02}
BANKS = ("experts_gate", "experts_up", "experts_down")


def build_model(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.models.mimo import MimoLM

    return MimoLM(
        num_classes=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_kinds=tuple(cfg["hybrid_layer_pattern"]),
        ffn_kinds=tuple(cfg["moe_layer_freq"]),
        heads=cfg["num_attention_heads"], heads_kv=cfg["num_key_value_heads"],
        window_heads_kv=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        rotary_dim=int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        window_rope_theta=float(cfg["swa_rope_theta"]),
        window=cfg["sliding_window"], value_scale=cfg["attention_value_scale"],
        intermediate=cfg["intermediate_size"],
        expert_intermediate=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts_published"],
        top_k=cfg["num_experts_per_tok"],
        held_first=cfg["deployment"]["held_first"],
        held_experts=cfg["n_routed_experts"],
        norm_eps=cfg["layernorm_epsilon"],
        dtype=jnp.float32 if rehearse else jnp.bfloat16)


def mimo_weights(model, seed: int, dtype):
    """``make_weights``, then the leaves it sizes by their first axis put to
    their own scale, in place: an expert bank (held, fan_in, fan_out) to
    1/sqrt(fan_in), sinks and correction biases to ``LEAF_STD``."""
    import jax

    def rescale(params):
        def fix(path, x):
            name = str(getattr(path[-1], "key", path[-1]))
            if name in BANKS:
                target = x.shape[1] ** -0.5
            elif name in LEAF_STD:
                target = LEAF_STD[name]
            else:
                return x
            return (x * (target / x.shape[0] ** -0.5)).astype(x.dtype)
        return jax.tree_util.tree_map_with_path(fix, params)

    params = jax.jit(rescale, donate_argnums=0)(make_weights(model, seed, dtype))
    jax.block_until_ready(params)
    return params


def observe(engine, cell) -> dict:
    """The check's requests through the engine, and what the engine held
    for them besides their tokens: after every decode step the experts each
    expert layer chose for the row (the ``chosen`` leaves), at the end each
    window layer's rings at the row's slot, and over all of it the device's
    own ``expert_load`` and the host's count of the pairs routed."""
    import jax

    cfg, spec = cell.config, cell.config["check"]
    n_new = int(spec["new"])
    rng = np.random.default_rng([cell.seed, 7])
    prompts = [rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
               for n in spec["prompts"]]
    layers = list(zip(cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]))
    windowed = [f"block_{i}" for i, (kind, _) in enumerate(layers) if kind]
    expert = [f"block_{i}" for i, (_, ffn) in enumerate(layers) if ffn]

    def counted():
        engine.sync_expert_load()
        s = engine.stats.summary()
        return np.asarray(s["expert_load"], np.int64), s["expert_assignments"]

    load0, pairs0 = counted()
    reqs = [engine.submit(p, max_new=n_new) for p in prompts]
    slots: dict[int, int] = {}
    seen = [0] * len(reqs)
    chosen = [[] for _ in reqs]   # per request: (position, ids (L, top_k))

    def after_step():
        for i, r in enumerate(reqs):
            if r in engine._slot_req:
                slots[i] = engine._slot_req.index(r)
            n = len(r.generated)
            # the first token is the last chunk's; each later one a decode
            # step whose query is the token before it
            if n > max(seen[i], 1):
                ids = jax.device_get(
                    [engine.cache[b]["chosen"][slots[i]] for b in expert])
                chosen[i].append((r.tokens.size + n - 2, np.stack(ids)))
            seen[i] = n

    drive(engine, lambda: all(r.status in ("done", "failed", "cancelled") for r in reqs),
          after_step)
    ok = all(r.status == "done" and len(r.generated) == n_new for r in reqs)
    rings = [{k: jax.device_get([engine.cache[b][k][slots[i]] for b in windowed])
              for k in ("ring_k", "ring_v")}
             for i in range(len(reqs))] if ok else []
    load1, pairs1 = counted()
    return {"ok": ok, "prompts": prompts, "reqs": reqs, "chosen": chosen,
            "rings": rings, "load": load1 - load0, "pairs": pairs1 - pairs0}


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(seen: dict, params, cfg: dict, low: tuple = ()) -> dict:
    """What ``observe`` saw against ONE plain-reference forward a request.
    Six numbers, each against its limit in the configuration's ``check``:

    greedy_gap, logprob_err  every emitted token within ``tolerance`` logits
        of the reference's argmax, its log-probability within it
    expert_overlap  the least, over expert layers, of the share of the
        experts the decode steps chose that the reference chose for the
        same token (mean over requests and steps)
    ring_err  the largest relative distance (Frobenius), over (request,
        window layer, K or V), of a row's ring in the engine's cache from
        the reference's keys and values of the last 128 positions fed
    load_err  the device's ``expert_load`` over the check's tokens against
        the reference's count of the pairs each held expert gets: summed
        absolute difference over the reference's total
    pairs  the host's ``expert_assignments`` over the same tokens equals
        tokens fed x top-k x expert layers

    ``low`` names what the reference lowers or drops (``reference_mimo.LOW``;
    all of it is the control, which has to come out NOT ok).  Overlap and
    ring error are also given per layer (``by_layer``), first layer first."""
    import jax

    spec, lim = cfg["check"], cfg["check"]["limits"]
    shape = reference_mimo.shape_of(cfg)
    window, top_k = cfg["sliding_window"], cfg["num_experts_per_tok"]
    n_held, first = cfg["n_routed_experts"], cfg["deployment"]["held_first"]
    n_expert = sum(cfg["moe_layer_freq"])
    gap = err = 0.0
    fed_total = 0
    ring_errs, overlaps = [], []    # per request, a number a layer
    want_load = np.zeros((n_expert, n_held), np.int64)
    for p, r, chosen, rings in zip(seen["prompts"], seen["reqs"], seen["chosen"],
                                   seen["rings"]):
        g = np.asarray(r.generated, np.int32)
        fed = p.size + g.size - 1     # tokens the engine consumed: all but the last
        fed_total += fed
        audit = {"ring_at": fed}
        # row t predicts token t + 1
        at = np.asarray(reference_mimo.logits_rows(
            params, np.concatenate([p, g]),
            np.arange(p.size - 1, p.size - 1 + g.size), shape, audit, low=tuple(low)))
        got = at[np.arange(g.size), g]
        gap = max(gap, float(np.max(at.max(-1) - got)))
        logp = got - np.asarray(jax.nn.logsumexp(at, axis=-1))
        err = max(err, float(np.max(np.abs(logp - np.asarray(r.logprobs)))))
        # position q of the last ``window`` fed lives at ring slot q mod window
        at_slot = np.arange(max(fed - window, 0), fed) % window
        ring_errs.append([
            max(_rel(np.asarray(rings[k][layer], np.float32)[at_slot], want)
                for k in ("ring_k", "ring_v") for want in [audit[k][layer]])
            for layer in range(len(audit["ring_k"]))])
        hit = np.zeros(n_expert)
        for t, ids in chosen:
            for layer, want in enumerate(audit["chosen"]):
                hit[layer] += np.isin(ids[layer], want[t]).sum()
        if chosen:
            overlaps.append(hit / (len(chosen) * top_k))
        for layer, want in enumerate(audit["chosen"]):
            local = want[:fed].reshape(-1) - first
            want_load[layer] += np.bincount(
                local[(local >= 0) & (local < n_held)], minlength=n_held)
    by_layer = {"ring_err": np.max(ring_errs, 0).tolist() if ring_errs else [],
                "expert_overlap": np.min(overlaps, 0).tolist() if overlaps else []}
    ring_err = max(by_layer["ring_err"], default=0.0)
    overlap = min(by_layer["expert_overlap"], default=1.0)
    load = np.asarray(seen["load"], np.int64).reshape(want_load.shape)
    load_err = float(np.abs(load - want_load).sum() / max(want_load.sum(), 1))
    pairs_want = fed_total * top_k * n_expert
    tol = float(spec["tolerance"])
    ok = bool(seen["ok"] and gap <= tol and err <= tol
              and overlap >= lim["expert_overlap_min"]
              and ring_err <= lim["ring_err_max"]
              and load_err <= lim["load_err_max"]
              and seen["pairs"] == pairs_want and load.sum() > 0)
    return {"ok": ok, "greedy_gap": gap, "logprob_err": err, "tolerance": tol,
            "expert_overlap": overlap, "ring_err": ring_err, "load_err": load_err,
            "pairs_counted": int(seen["pairs"]), "pairs_expected": int(pairs_want),
            "held_on_device": int(load.sum()), "held_in_reference": int(want_load.sum()),
            "limits": lim, "by_layer": by_layer, "requests": len(seen["reqs"]),
            "check_prompts": list(spec["prompts"])}


def check(engine, cell) -> dict:
    """The cell's correctness check (the module's docstring)."""
    return compare(observe(engine, cell), engine.params, cell.config)


def build_engine(cell: harness.Cell, setup: harness.Setup):
    """Weights from ``--seed``, the engine, ``prewarm()``."""
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_ibm_mnist_tpu.serving.scheduler import FIFOScheduler

    if cell.rehearse:
        from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret

        set_interpret(True)
    cfg, opts = cell.config, cell.config["engine"]
    if int(cell.traffic.get("prefill_chunk", opts["prefill_chunk"])) != opts["prefill_chunk"]:
        raise SystemExit(
            f"the traffic's prefill_chunk {cell.traffic['prefill_chunk']} is "
            f"not the configuration's {opts['prefill_chunk']}")
    model = build_model(cfg, cell.rehearse)
    params = mimo_weights(model, cell.jax_seed(),
                          jnp.float32 if cell.rehearse else jnp.bfloat16)
    setup.mark("weights")
    engine = InferenceEngine(
        model, params, slots=opts["slots"], max_len=opts["max_len"],
        scheduler=FIFOScheduler(max_len=opts["max_len"],
                                buckets=tuple(opts["buckets"]),
                                max_queue=opts["max_queue"],
                                chunked_prefill=True),
        decode_ahead=opts["decode_ahead"], kv_page_size=opts["kv_page_size"],
        kv_pages=opts["kv_pages"], prefill_chunk=opts["prefill_chunk"])
    warm = engine.prewarm()
    setup.mark("prewarm")
    return engine, warm["programs"]


def mixed_tables(traffic: dict, per_client: int) -> dict:
    """A closed loop's work when prompt lengths are a MIXTURE: each part of
    ``prompt_len["parts"]`` is drawn whole by the generator that is there
    (``traffic.closed_tables``, one length distribution at a time), and each
    request takes its length from the part a draw of its own picks, by the
    parts' shares.  Answers and first cuts are the first part's table."""
    parts = traffic["prompt_len"]["parts"]
    tabs = [traffic_gen.closed_tables({**traffic, "prompt_len": part}, per_client)
            for part in parts]
    edges = np.cumsum([part["share"] for part in parts])
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 1])
    pick = np.searchsorted(edges, rng.random(tabs[0]["prompt_len"].shape) * edges[-1],
                           side="right").clip(max=len(parts) - 1)
    return {"prompt_len": np.choose(pick, [t["prompt_len"] for t in tabs]),
            "max_new": tabs[0]["max_new"]}


class MixedLoop(LongDocLoop):
    """``LongDocLoop`` (every client's first request admitted and answering
    before the window) over ``mixed_tables``, with the counters of the
    window's rings, global pages and experts."""

    def __init__(self, engine, cell, vocab):
        parts = cell.traffic["prompt_len"]["parts"]
        # the parent draws ONE distribution; its table is replaced at once
        super().__init__(engine, dataclasses.replace(
            cell, traffic={**cell.traffic, "prompt_len": parts[0]}), vocab)
        self.tab = mixed_tables(cell.traffic, self.PER_CLIENT)

    def run_window(self, profiler, t0, t1, on_step) -> dict:
        self.engine.sync_expert_load()   # outside the stepped window
        s0 = self.engine.stats.summary()
        counters = super().run_window(profiler, t0, t1, on_step)
        self.engine.sync_expert_load()
        s1 = self.engine.stats.summary()
        for k in ("global_pages_read", "expert_assignments",
                  "expert_assignments_held"):
            counters[k] = s1[k] - s0[k]
        for k in ("expert_load", "expert_hits"):
            counters[k] = (np.asarray(s1[k]) - np.asarray(s0[k])).tolist()
        counters["ring_rows_in_use"] = s1["ring_rows_in_use"]
        counters["ring_rows_total"] = s1["ring_rows_total"]
        return counters


def run(cell: harness.Cell, devs, setup: harness.Setup) -> dict:
    tracker = harness.compile_tracker()
    c0 = tracker.snapshot()
    setup.mark("import")
    engine, programs = build_engine(cell, setup)
    chk = check(engine, cell)
    setup.mark("check")

    client = MixedLoop(engine, cell, cell.config["vocab_size"])
    client.warm_in()
    setup.mark("warm_in")
    c1 = tracker.snapshot()

    profiler = harness.ProfilerWindow(cell)
    out = client.window(profiler)
    c2 = tracker.snapshot()
    engine.close()

    built = harness.compile_delta(c1, c0)
    counters = {
        "compile_s": built["compile_s"], "setup_programs": built["programs"],
        "setup_cache_hits": built["cache_hits"],
        "window_compiles": harness.compile_delta(c2, c1)["programs"],
        "prewarm_programs": programs,
        **out["counters"],
    }
    out["end_to_end"]["setup_s"] = setup.total()
    return {
        "correct": bool(chk["ok"] and out["failed"] == 0 and out["attempted"] > 0),
        "attempted": out["attempted"], "failed": out["failed"],
        "end_to_end": out["end_to_end"], "counters": counters,
        "check": {**chk, **out.get("notes", {}),
                  **{k: counters[k] for k in (
                      "global_pages_read", "expert_assignments",
                      "expert_assignments_held", "expert_load",
                      "n_prefill_chunks", "n_windows", "decode_batch_mean",
                      "kv_pool_fill_share", "itl_p95_s", "ttft_p90_s")}},
        "setup": {**{k: round(v, 3) for k, v in setup.items.items()},
                  "setup_s": round(setup.total(), 3), **built},
        "profiler": profiler,
    }
