"""The Nemotron-3-Super serving cell: one chip's share of a 4-way
expert-parallel stage through ``InferenceEngine`` with ``prewarm()``,
chunked prefill and the state pool, under the closed-loop client of
``mimo_serve_runner`` with mixed prompt lengths.

Set-up: bf16 weights made on the device from ``--seed``; the engine (a
state and a convolution tail a slot for each Mamba layer, a page pool for
the attention layer, the held experts' banks) and its ``prewarm()`` (two
programs: the decode window and the one extend chunk); the correctness check
at the timed widths on the timed path; the warm-in that admits every
client's first request.  Then the window.

The check (it decides ``correct``): every slot taken, the judged prompts
(one inside a chunk, one over four chunk edges) decoded beside fillers in
all other slots, chunk-prefilled and decoded through the caches; a sample of
the requests (the judged ones and fillers spread over the slots) each
against ONE plain-reference forward (``reference_nemotron``) of prompt +
answer, routed at every token to the experts the engine chose there.
Beside the logits it reads what the new mechanisms PRODUCE out of the
engine's cache: each Mamba layer's state (whole and its worst head) and
convolution tail at the row's slot at the end, the experts each chunk and
decode step chose, the device's own count of the pairs its held experts
computed, and the host's counts of the tokens and row-steps through the two
SSD kernels.  The limits, each between the engine's reading and the
control's (the reference with float8 weights, or with a bf16 state, step
sizes and decays), are in the configuration file's ``check`` and
``PERF.md``; ``benchmark/tests/control_nemotron.py`` runs the controls,
which have to fail.
"""

from __future__ import annotations

import zlib

import numpy as np

from benchmark import harness, reference_nemotron
from benchmark.mimo_serve_runner import MixedLoop
from benchmark.serve_runner import drive, make_weights

# the leaves ``make_weights`` cannot size by their first axis: a bank's
# fan-in is its second axis; the correction bias is drawn as MiMo's (a
# fifth of the band of scores the chosen experts lie in); the Mamba
# layers' A_log, dt_bias and D by the published initialisation
ROUTER_BIAS_STD = 0.02
BANKS = ("experts_up", "experts_down")


def build_model(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.models import get_model

    return get_model(
        "nemotron_h", num_classes=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"], heads=cfg["mamba_num_heads"],
        head_dim=cfg["mamba_head_dim"], state=cfg["ssm_state_size"],
        groups=cfg["n_groups"], conv_kernel=cfg["conv_kernel"],
        attn_heads=cfg["num_attention_heads"],
        attn_heads_kv=cfg["num_key_value_heads"], attn_head_dim=cfg["head_dim"],
        latent=cfg["moe_latent_size"],
        expert_intermediate=cfg["moe_intermediate_size"],
        shared_intermediate=cfg["moe_shared_expert_intermediate_size"],
        n_experts=cfg["n_routed_experts_published"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        held_first=cfg["deployment"]["held_first"],
        held_experts=cfg["n_routed_experts"],
        norm_eps=cfg["layer_norm_epsilon"],
        # the check reads each chunk's expert choices (observe)
        record_chunk=cfg["engine"]["prefill_chunk"],
        dtype=jnp.float32 if rehearse else jnp.bfloat16)


def nemotron_weights(model, seed: int, dtype, cfg: dict):
    """``make_weights``, then in place: an expert bank (held, fan_in,
    fan_out) to 1/sqrt(fan_in), the correction bias to
    ``ROUTER_BIAS_STD``, and each Mamba layer's ``A_log = log U(1, 16)``,
    ``dt_bias = softplus^-1(dt)`` with ``log dt ~ U(log time_step_min, log
    time_step_max)`` floored at ``time_step_floor``, and ``D = 1`` (the
    published initialisation; the config names the step limits)."""
    import jax
    import jax.numpy as jnp

    lo, hi = np.log(cfg["time_step_min"]), np.log(cfg["time_step_max"])
    floor = cfg["time_step_floor"]
    key = jax.random.PRNGKey(seed)

    def rescale(params):
        def fix(path, x):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()))
            if name in BANKS:
                return (x * (x.shape[1] ** -0.5 / x.shape[0] ** -0.5)).astype(x.dtype)
            if name == "router_bias":
                return (x * (ROUTER_BIAS_STD / x.shape[0] ** -0.5)).astype(x.dtype)
            if name == "A_log":
                return jnp.log(jax.random.uniform(k, x.shape, jnp.float32, 1, 16)).astype(x.dtype)
            if name == "dt_bias":
                dt = jnp.maximum(jnp.exp(jax.random.uniform(k, x.shape, jnp.float32, lo, hi)),
                                 floor)
                return (dt + jnp.log(-jnp.expm1(-dt))).astype(x.dtype)
            if name == "D":
                return jnp.ones_like(x)
            return x
        return jax.tree_util.tree_map_with_path(fix, params)

    params = jax.jit(rescale, donate_argnums=0)(make_weights(model, seed, dtype))
    jax.block_until_ready(params)
    return params


def _blocks(cfg: dict, kind: str) -> list[str]:
    return [f"block_{i}" for i, c in enumerate(cfg["hybrid_override_pattern"])
            if c == kind]


def observe(engine, cell) -> dict:
    """The check's requests through the engine, and what the engine held
    for them besides their tokens.

    Every slot is taken: ``slots - len(prompts)`` fillers (short prompts,
    ``fillers["new"]`` tokens each) are submitted first and are still
    decoding while the judged prompts are prefilled and decoded, so the
    judged decode steps run with every row live.  For EVERY request the
    engine's own expert choices are read at each of its tokens: a prefill
    chunk's from the ``chunk_chosen`` leaves after the step that ran it (one
    chunk an engine iteration), a decode step's from the ``chosen`` leaves.
    At the end each sampled request's Mamba states and convolution tails
    are read at its slot, and over all of it the device's own
    ``expert_load`` and the host's counts of pairs, scanned tokens and
    updated row-steps."""
    import jax

    cfg, spec = cell.config, cell.config["check"]
    fill = spec["fillers"]
    rng = np.random.default_rng([cell.seed, 7])
    judged = [rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
              for n in spec["prompts"]]
    n_fill = engine.slots - len(judged)
    fillers = [rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
               for n in rng.integers(fill["prompt"][0], fill["prompt"][1] + 1, n_fill)]
    expert = _blocks(cfg, "E")
    keys = ("expert_assignments", "ssm_scan_tokens", "ssm_step_rows")

    def counted():
        engine.sync_expert_load()
        s = engine.stats.summary()
        return np.asarray(s["expert_load"], np.int64), {k: s[k] for k in keys}

    load0, host0 = counted()
    # fillers first: FIFO admission gives them the other slots before the
    # judged requests arrive
    reqs = ([engine.submit(p, max_new=int(fill["new"])) for p in fillers]
            + [engine.submit(p, max_new=int(spec["new"])) for p in judged])
    prompts = fillers + judged
    sample = sorted(set(np.linspace(0, n_fill - 1, int(fill["sampled"])).astype(int))
                    ) + list(range(n_fill, len(reqs)))
    slot_of: dict[int, int] = {}
    done = [0] * len(reqs)       # prompt tokens whose chunk has run
    seen = [0] * len(reqs)       # tokens generated at the last look
    # per request, the ids (n, top_k) of each expert layer at each fed token
    chunks = [[] for _ in reqs]   # (start, ids (L, n, top_k))
    steps = [[] for _ in reqs]    # (position, ids (L, top_k))
    live_rows = []   # rows that decoded in each step a judged request decoded in
    faults = []

    def after_step():
        where = {id(r): i for i, r in enumerate(engine._slot_req) if r is not None}
        advanced, decoded = [], []
        for i, r in enumerate(reqs):
            if id(r) in where:
                slot_of[i] = where[id(r)]
            if i not in slot_of:
                continue
            rec = engine._slot_prefill[slot_of[i]] if id(r) in where else None
            now = (rec["done"] if rec is not None and engine._slot_req[slot_of[i]] is r
                   else prompts[i].size)
            if now > done[i]:
                advanced.append((i, done[i], now))
                done[i] = now
            n = len(r.generated)
            # the first token is the last chunk's; each later one a decode
            # step whose query is the token before it
            if n > max(seen[i], 1):
                if n - max(seen[i], 1) > 1:
                    faults.append(f"request {i} took {n - seen[i]} tokens in one step")
                decoded.append((i, n))
            seen[i] = n
        if len(advanced) > 1:
            faults.append(f"{len(advanced)} chunks in one step")
        if advanced:
            ids = np.stack(jax.device_get([engine.cache[b]["chunk_chosen"] for b in expert]))
            for i, a, z in advanced:
                chunks[i].append((a, ids[:, :z - a]))
        if decoded:
            ids = np.stack(jax.device_get([engine.cache[b]["chosen"] for b in expert]))
            for i, n in decoded:
                steps[i].append((prompts[i].size + n - 2, ids[:, slot_of[i]]))
            if any(i >= n_fill for i, _ in decoded):
                live_rows.append(len(decoded))

    drive(engine, lambda: all(r.status in ("done", "failed", "cancelled") for r in reqs),
          after_step)
    ok = all(r.status == "done" and len(r.generated) == r.max_new for r in reqs)
    # every request had a slot of its own (slots = requests), which keeps
    # its leaves once the request is done: an idle row's state stands
    held = {i: {k: jax.device_get([engine.cache[b][k][slot_of[i]]
                                   for b in _blocks(cfg, "M")])
                for k in ("ssm_state", "conv_state")}
            for i in sample} if ok else {}
    load1, host1 = counted()
    # the device's counters come in the cache's order of layer names
    # (block_10 before block_3), the reference's in the pattern's
    order = [expert.index(name) for name in engine.cache if name in expert]
    return {"ok": ok and not faults and len(held) == len(sample)
            and min(live_rows, default=0) >= n_fill,
            "faults": faults, "prompts": prompts, "reqs": reqs,
            "sample": sample, "judged": list(range(n_fill, len(reqs))),
            "routes": [_routes(c, st, len(expert), cfg["num_experts_per_tok"])
                       for c, st in zip(chunks, steps)],
            "held": held, "live_rows": live_rows,
            "load": (load1 - load0)[np.argsort(order)],
            "host": {k: host1[k] - host0[k] for k in keys}}


def _routes(chunks, steps, n_layers, top_k) -> np.ndarray:
    """(L, fed, top_k) the engine's expert ids at each fed token, from the
    chunks' and the decode steps' records; positions nobody recorded hold
    -1."""
    fed = max([a + ids.shape[1] for a, ids in chunks] + [t + 1 for t, _ in steps] + [0])
    out = np.full((n_layers, fed, top_k), -1, np.int32)
    for a, ids in chunks:
        out[:, a:a + ids.shape[1]] = ids
    for t, ids in steps:
        out[:, t] = ids
    return out


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _head_rel(a, b) -> float:
    """The largest relative distance of one head's (P, N) state."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    d = np.linalg.norm((a - b).reshape(a.shape[0], -1), axis=1)
    return float(np.max(d / np.linalg.norm(b.reshape(b.shape[0], -1), axis=1)))


def compare(seen: dict, params, cfg: dict, low: tuple = ()) -> dict:
    """What ``observe`` saw against ONE plain-reference forward a sampled
    request, routed at each token to the experts the engine chose there
    (``reference_nemotron.logits_rows(..., forced=)``): a choice the two
    part on at a near-tie of 512 sigmoid scores is then not carried into
    every later layer, and every layer is judged.  Each number against its
    limit in the configuration's ``check``:

    greedy_gap, logprob_err  every emitted token within ``tolerance`` logits
        of the reference's argmax, its log-probability within it
    state_err, state_head_err, conv_err  per Mamba layer, the largest over
        sampled requests of the relative distance (Frobenius) of the row's
        SSM state in the engine's cache from the reference's after the same
        tokens: the whole state, and its worst head (a slowly decaying head
        is where a state kept in bf16 drifts); and of the convolution tail
    expert_overlap  per expert layer, the least over sampled requests of
        the share of the experts the engine chose at the fed tokens that the
        reference's own router chose for the same token
    load_err  the device's ``expert_load`` over the check's tokens against
        the count of the held experts among the engine's own recorded
        choices at every fed token of every request: summed absolute
        difference over the total (exact: 0)
    counts  the host's ``expert_assignments``, ``ssm_scan_tokens`` and
        ``ssm_step_rows`` over the same requests equal tokens fed x top-k x
        expert layers, prompt tokens x Mamba layers and decode steps x Mamba
        layers

    The per-layer limits are lists, first layer first (``by_layer`` holds
    the readings).  ``low`` names what the reference lowers
    (``reference_nemotron.LOW``; each is a control, which has to come out
    NOT ok)."""
    import jax

    spec, lim = cfg["check"], cfg["check"]["limits"]
    shape = reference_nemotron.shape_of(cfg)
    top_k = cfg["num_experts_per_tok"]
    n_held, first = cfg["n_routed_experts"], cfg["deployment"]["held_first"]
    n_expert = cfg["hybrid_override_pattern"].count("E")
    n_mamba = cfg["hybrid_override_pattern"].count("M")
    gap = err = 0.0
    want_host = {"expert_assignments": 0, "ssm_scan_tokens": 0, "ssm_step_rows": 0}
    want_load = np.zeros((n_expert, n_held), np.int64)
    complete = True
    for p, r, route in zip(seen["prompts"], seen["reqs"], seen["routes"]):
        g = np.asarray(r.generated, np.int32)
        fed = p.size + g.size - 1     # tokens the engine consumed: all but the last
        want_host["expert_assignments"] += fed * top_k * n_expert
        want_host["ssm_scan_tokens"] += p.size * n_mamba
        want_host["ssm_step_rows"] += (g.size - 1) * n_mamba
        complete &= route.shape[1] == fed and bool((route >= 0).all())
        local = route - first
        for layer in range(n_expert):
            hit = local[layer][(local[layer] >= 0) & (local[layer] < n_held)]
            want_load[layer] += np.bincount(hit, minlength=n_held)
    states, heads, convs, overlaps = [], [], [], []    # per request, a number a layer
    for i in seen["sample"]:
        p, r, route, held = (seen["prompts"][i], seen["reqs"][i], seen["routes"][i],
                             seen["held"].get(i))
        if held is None or not complete:
            break
        g = np.asarray(r.generated, np.int32)
        audit = {"state_at": p.size + g.size - 1}
        # row t predicts token t + 1
        at = np.asarray(reference_nemotron.logits_rows(
            params, np.concatenate([p, g]),
            np.arange(p.size - 1, p.size - 1 + g.size), shape, audit, low=tuple(low),
            forced=list(route)))
        got = at[np.arange(g.size), g]
        gap = max(gap, float(np.max(at.max(-1) - got)))
        logp = got - np.asarray(jax.nn.logsumexp(at, axis=-1))
        err = max(err, float(np.max(np.abs(logp - np.asarray(r.logprobs)))))
        states.append([_rel(a, b) for a, b in zip(held["ssm_state"], audit["ssm_state"])])
        heads.append([_head_rel(a, b) for a, b in zip(held["ssm_state"], audit["ssm_state"])])
        convs.append([_rel(a, b) for a, b in zip(held["conv_state"], audit["conv_state"])])
        overlaps.append([
            float((route[layer][:, :, None] == want[:route.shape[1], None, :])
                  .any(-1).mean())
            for layer, want in enumerate(audit["chosen"])])
    by_layer = {"state_err": np.max(states, 0).tolist() if states else [],
                "state_head_err": np.max(heads, 0).tolist() if heads else [],
                "conv_err": np.max(convs, 0).tolist() if convs else [],
                "expert_overlap": np.min(overlaps, 0).tolist() if overlaps else []}
    load = np.asarray(seen["load"], np.int64).reshape(want_load.shape)
    load_err = float(np.abs(load - want_load).sum() / max(want_load.sum(), 1))
    tol = float(spec["tolerance"])

    def within(key, limit, above):
        return len(by_layer[key]) == len(limit) and all(
            v >= m if above else v <= m for v, m in zip(by_layer[key], limit))

    ok = bool(seen["ok"] and complete and len(states) == len(seen["sample"])
              and gap <= tol and err <= tol
              and within("state_err", lim["state_err_max"], False)
              and within("state_head_err", lim["state_head_err_max"], False)
              and within("conv_err", lim["conv_err_max"], False)
              and within("expert_overlap", lim["expert_overlap_min"], True)
              and load_err <= lim["load_err_max"]
              and seen["host"] == want_host and load.sum() > 0)
    return {"ok": ok, "greedy_gap": gap, "logprob_err": err, "tolerance": tol,
            **{k: (max(v) if k != "expert_overlap" else min(v)) if v else None
               for k, v in by_layer.items()},
            "load_err": load_err, "counted": seen["host"], "expected": want_host,
            "held_on_device": int(load.sum()), "held_in_routes": int(want_load.sum()),
            "routes_complete": bool(complete), "faults": seen["faults"],
            "limits": lim, "by_layer": by_layer, "requests": len(seen["reqs"]),
            "sampled": len(seen["sample"]), "check_prompts": list(spec["prompts"]),
            "live_rows_min": min(seen["live_rows"], default=0)}


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
    params = nemotron_weights(model, cell.jax_seed(),
                              jnp.float32 if cell.rehearse else jnp.bfloat16, cfg)
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


class AgentLoop(MixedLoop):
    """``MixedLoop`` (every client's first request admitted and answering
    before the window, prompts a mixture) with the counters of the two SSD
    kernels."""

    def run_window(self, profiler, t0, t1, on_step) -> dict:
        s0 = self.engine.stats.summary()
        counters = super().run_window(profiler, t0, t1, on_step)
        s1 = self.engine.stats.summary()
        for k in ("ssm_scan_tokens", "ssm_step_rows"):
            counters[k] = s1[k] - s0[k]
        return counters


def run(cell: harness.Cell, devs, setup: harness.Setup) -> dict:
    tracker = harness.compile_tracker()
    c0 = tracker.snapshot()
    setup.mark("import")
    engine, programs = build_engine(cell, setup)
    chk = check(engine, cell)
    setup.mark("check")

    client = AgentLoop(engine, cell, cell.config["vocab_size"])
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
                      "ssm_scan_tokens", "ssm_step_rows", "expert_assignments",
                      "expert_assignments_held", "expert_load",
                      "n_prefill_chunks", "n_windows", "decode_batch_mean",
                      "kv_pool_fill_share", "state_rows_in_use",
                      "state_rows_total", "itl_p95_s", "ttft_p90_s")}},
        "setup": {**{k: round(v, 3) for k, v in setup.items.items()},
                  "setup_s": round(setup.total(), 3), **built},
        "profiler": profiler,
    }
