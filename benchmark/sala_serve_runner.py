"""The MiniCPM-SALA serving cell: the configuration through
``InferenceEngine`` with ``prewarm()``, chunked prefill, under the
closed-loop client of ``serve_runner``.

Set-up: bf16 weights made on the device from ``--seed``; the engine (page
pool for the sparse layers, one state row a slot for the lightning layers)
and its ``prewarm()`` (two programs: the decode window and the one extend
chunk); the correctness check at the timed widths on the timed path; the
warm-in that admits every client's first request.  Then the window.

The check (it decides ``correct``): two requests whose prompts are longer
than ``dense_len``, chunk-prefilled and decoded through the caches, each
against ONE plain-reference forward (``reference_sala``) of prompt + answer.
Logits alone cannot hold this model's new mechanisms (with random weights
attention is near-uniform and the head divides by 16: any 64 blocks and a
bf16 state read the same logits to a few thousandths), so the check also
reads what those mechanisms PRODUCE out of the engine's cache — the block
ids handed to the decode kernel, the lightning states, the compressed keys
— and holds each against the reference's (``compare``).  The limits, each
between the engine's reading and the control's (the reference in the
precision below), are in the configuration file's ``check`` and ``PERF.md``;
``benchmark/tests/control_sala.py`` runs the control, which has to fail.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness, reference_sala
from benchmark.serve_runner import ClosedLoop, drive, make_weights


def build_model(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.models.sala import SalaLM
    from distributed_tensorflow_ibm_mnist_tpu.ops.sparse_attention import SparseSpec

    return SalaLM(
        num_classes=cfg["vocab_size"], dim=cfg["hidden_size"],
        mixer_types=tuple(cfg["mixer_types"]),
        heads=cfg["num_attention_heads"], heads_kv=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], lightning_heads=cfg["lightning_nh"],
        intermediate=cfg["intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), scale_emb=float(cfg["scale_emb"]),
        scale_depth=float(cfg["scale_depth"]),
        residual_layers=cfg["residual_layers"],
        logit_divisor=cfg["hidden_size"] / cfg["dim_model_base"],
        sparse=SparseSpec(**cfg["assumed_sizes"]["sparse_config"]),
        dtype=jnp.float32 if rehearse else jnp.bfloat16)


def observe(engine, cell) -> dict:
    """The check's requests through the engine, and what the engine held
    for them besides their tokens: after every decode step the block ids and
    list lengths the paged kernel was handed for each row (the sparse
    layers' ``sel`` / ``sel_len`` leaves), at the end each row's lightning
    states and compressed keys, read out of the engine's cache at the row's
    slot, and the program's own count of the pages it read."""
    import jax

    cfg, spec = cell.config, cell.config["check"]
    n_new = int(spec["new"])
    rng = np.random.default_rng([cell.seed, 7])
    prompts = [rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
               for n in spec["prompts"]]
    sparse = [f"block_{i}" for i, m in enumerate(cfg["mixer_types"]) if m == "minicpm4"]
    lightning = [f"block_{i}" for i, m in enumerate(cfg["mixer_types"]) if m != "minicpm4"]
    read0 = engine.stats.summary()["sparse_blocks_read"]
    reqs = [engine.submit(p, max_new=n_new) for p in prompts]
    slots: dict[int, int] = {}
    seen = [0] * len(reqs)
    picked = [[] for _ in reqs]   # per request: (position, ids (L, Hkv, W), lengths (L, Hkv))

    def after_step():
        for i, r in enumerate(reqs):
            if r in engine._slot_req:
                slots[i] = engine._slot_req.index(r)
            n = len(r.generated)
            # the first token is the last chunk's; each later one a decode
            # step whose query is the token before it
            if n > max(seen[i], 1):
                ids, lens = jax.device_get(
                    [[engine.cache[b][k][slots[i]] for b in sparse]
                     for k in ("sel", "sel_len")])
                picked[i].append((r.tokens.size + n - 2, np.stack(ids), np.stack(lens)))
            seen[i] = n

    drive(engine, lambda: all(r.status in ("done", "failed", "cancelled") for r in reqs),
          after_step)
    ok = all(r.status == "done" and len(r.generated) == n_new for r in reqs)
    held = [{k: jax.device_get([engine.cache[b][k][slots[i]] for b in names])
             for k, names in (("state", lightning), ("kc", sparse))}
            for i in range(len(reqs))] if ok else []
    return {"ok": ok, "prompts": prompts, "reqs": reqs, "picked": picked,
            "held": held,
            "pages_counted": engine.stats.summary()["sparse_blocks_read"] - read0}


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(seen: dict, params, cfg: dict, low: tuple = ()) -> dict:
    """What ``observe`` saw against ONE plain-reference forward a request.
    Six numbers, each against its limit in the configuration's ``check``:

    greedy_gap, logprob_err  every emitted token within ``tolerance`` logits
        of the reference's argmax, its log-probability within it
    selection_overlap  the least share, over (request, sparse layer), of the
        top-k block ids the decode kernel was handed that the reference's
        float32 selection at the same position holds
    state_err, kc_err  the largest relative distance (Frobenius), over
        (request, layer), of a row's lightning state / compressed keys in
        the engine's cache from the reference's after the same tokens
    pages  the pages the device's lists name (``sel_len``) equal the
        program's ``sparse_blocks_read`` counter over the same steps

    ``low`` names what the reference computes in the precision below the
    configuration's (``reference_sala.LOW``; all of it is the control, which
    has to come out NOT ok).  The three middle numbers are also given per
    layer (``by_layer``: the worst request's), first layer first."""
    import jax

    spec, lim = cfg["check"], cfg["check"]["limits"]
    shape = reference_sala.shape_of(cfg)
    sp = cfg["assumed_sizes"]["sparse_config"]
    ksz, stride, bsz, init, topk = (sp[k] for k in (
        "kernel_size", "kernel_stride", "block_size", "init_blocks", "topk"))
    gap = err = 0.0
    pages = 0
    states, kcs, overlaps = [], [], []    # per request, a number a layer
    for p, r, picked, held in zip(seen["prompts"], seen["reqs"], seen["picked"],
                                  seen["held"]):
        g = np.asarray(r.generated, np.int32)
        fed = p.size + g.size - 1     # tokens the engine consumed: all but the last
        audit = {"state_at": fed}
        # row t predicts token t + 1
        at = np.asarray(reference_sala.logits_rows(
            params, np.concatenate([p, g]),
            np.arange(p.size - 1, p.size - 1 + g.size), shape, audit, low=tuple(low)))
        got = at[np.arange(g.size), g]
        gap = max(gap, float(np.max(at.max(-1) - got)))
        logp = got - np.asarray(jax.nn.logsumexp(at, axis=-1))
        err = max(err, float(np.max(np.abs(logp - np.asarray(r.logprobs)))))
        states.append([_rel(a, b) for a, b in zip(held["state"], audit["state"])])
        n_k = (fed - ksz) // stride + 1   # kernels whose last token was fed
        kcs.append([_rel(a[:n_k], b[:n_k])
                    for a, b in zip(held["kc"], audit["kc"])])
        hit = np.zeros(len(audit["selected"]))
        for t, ids, lens in picked:
            pages += int((-(-lens // bsz)).sum())
            for layer, want in enumerate(audit["selected"]):
                if want[t].min() < 0:       # a dense position: every block is read
                    hit[layer] += topk * want.shape[1]
                    continue
                hit[layer] += sum(
                    np.isin(ids[layer, h, init:init + topk], want[t, h]).sum()
                    for h in range(want.shape[1]))
        if picked:
            overlaps.append(hit / (len(picked) * topk * audit["selected"][0].shape[1]))
    by_layer = {"state_err": np.max(states, 0).tolist() if states else [],
                "kc_err": np.max(kcs, 0).tolist() if kcs else [],
                "selection_overlap": np.min(overlaps, 0).tolist() if overlaps else []}
    state_err, kc_err = (max(by_layer[k], default=0.0) for k in ("state_err", "kc_err"))
    overlap = min(by_layer["selection_overlap"], default=1.0)
    tol = float(spec["tolerance"])
    ok = bool(seen["ok"] and gap <= tol and err <= tol
              and state_err <= lim["state_err_max"] and kc_err <= lim["kc_err_max"]
              and overlap >= lim["selection_overlap_min"]
              and pages == seen["pages_counted"] > 0)
    return {"ok": ok, "greedy_gap": gap, "logprob_err": err, "tolerance": tol,
            "selection_overlap": overlap, "state_err": state_err, "kc_err": kc_err,
            "pages_on_device": pages, "pages_counted": seen["pages_counted"],
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
    params = make_weights(model, cell.jax_seed(),
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


def start_engine(cell: harness.Cell, setup: harness.Setup):
    engine, programs = build_engine(cell, setup)
    chk = check(engine, cell)
    setup.mark("check")
    return engine, {"prewarm_programs": programs, "check": chk}


class LongDocLoop(ClosedLoop):
    """The closed loop, warmed in until every client's first request has
    been admitted, prefilled and has answered its first token: the window
    opens on a full engine."""

    PER_CLIENT = 64

    def warm_in(self) -> None:
        for c in range(self.n_clients):
            self.send(c)
        first = list(self.live.values())
        drive(self.engine, lambda: all(
            r.times or r.req.status in ("failed", "cancelled") for r in first),
            self.refill)

    def run_window(self, profiler, t0, t1, on_step) -> dict:
        s0 = self.engine.stats.summary()
        first = len(self.recs)
        at_start = list(self.live.values())
        counters = super().run_window(profiler, t0, t1, on_step)
        s1 = self.engine.stats.summary()
        for k in ("sparse_blocks_read", "sparse_blocks_live", "dense_len_rows",
                  "n_prefill_chunks", "n_windows"):
            counters[k] = s1[k] - s0[k]
        # decoding rows x steps: the queries the decode kernel answered
        counters["decode_row_steps"] = s1["window_steps"] - s0["window_steps"]
        before = s0["prefill_chunk_starts"]
        counters["prefill_chunk_starts"] = {
            k: n - before.get(k, 0)
            for k, n in s1["prefill_chunk_starts"].items() if n > before.get(k, 0)}
        counters["state_rows_in_use"] = s1["state_rows_in_use"]
        counters["state_rows_total"] = s1["state_rows_total"]
        # what a reader of the answers feels, unjudged: every gap between a
        # request's successive tokens that ended in the window; and the wait
        # for the first token of every request that got it in the window or
        # was still waiting for it at the window's end (its wait so far)
        recs = at_start + self.recs[first:]
        counters["itl_p95_s"] = harness.percentile(
            [b - a for r in recs for a, b in zip(r.times, r.times[1:])
             if t0 <= b < t1], 95)
        counters["ttft_p90_s"] = harness.percentile(
            [(r.times[0] if r.times and r.times[0] < t1 else t1) - r.sent
             for r in recs if not r.times or r.times[0] >= t0], 90)
        return counters


def run(cell: harness.Cell, devs, setup: harness.Setup) -> dict:
    tracker = harness.compile_tracker()
    c0 = tracker.snapshot()
    setup.mark("import")
    engine, started = start_engine(cell, setup)
    chk = started["check"]

    client = LongDocLoop(engine, cell, cell.config["vocab_size"])
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
        "prewarm_programs": started["prewarm_programs"],
        **out["counters"],
    }
    out["end_to_end"]["setup_s"] = setup.total()
    return {
        "correct": bool(chk["ok"] and out["failed"] == 0 and out["attempted"] > 0),
        "attempted": out["attempted"], "failed": out["failed"],
        "end_to_end": out["end_to_end"], "counters": counters,
        "check": {**chk, **out.get("notes", {}),
                  **{k: counters[k] for k in (
                      "sparse_blocks_read", "sparse_blocks_live",
                      "dense_len_rows", "n_prefill_chunks", "n_windows",
                      "decode_batch_mean", "kv_pool_fill_share",
                      "itl_p95_s", "ttft_p90_s")}},
        "setup": {**{k: round(v, 3) for k, v in setup.items.items()},
                  "setup_s": round(setup.total(), 3), **built},
        "profiler": profiler,
    }
