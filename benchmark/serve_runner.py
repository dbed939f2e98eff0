"""The serving cells: the configuration through ``InferenceEngine`` with
``prewarm()``, under a closed-loop or a replayed open-loop client.

Set-up: bf16 weights made on the device from ``--seed`` in one jitted call;
the engine and its ``prewarm()``; the correctness check (four requests
against the plain reference); the page pool filled as a deployment's is
after minutes of service; the warm-in that brings the decode batch to its
steady size.  Then the window.

One thread drives ``engine.step()`` (the engine has one writer).  In the
open loop a second thread submits each request when it is due, so that a
busy engine loop does not make the generator late; how late it still ran is
reported (``gen_late_p90_s``).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import harness, reference, traffic as traffic_gen

# an emitted token may trail the reference's argmax by this many logits,
# and its log-probability differ from the reference's by as much: the bf16
# engine against the f32 reference read 0.003 and 0.035 on the v5e (PR 23),
# top-2 gaps of a random-init model are ~0.3, and a wrong cache row,
# position or mask is whole logits off
LOGIT_TOL = 0.1
CHECK_ROW = 256        # the reference's row: prompt + answer, padded
CHECK_PROMPTS = (97, 150, 201, 230)
CHECK_NEW = 16


class Rec:
    """One request as its client sees it."""

    __slots__ = ("index", "due", "sent", "times", "want", "req")

    def __init__(self, index, due, want):
        self.index, self.due, self.want = index, due, want
        self.sent = None
        self.times: list[float] = []
        self.req = None

    @property
    def done(self) -> bool:
        return len(self.times) >= self.want


def build_model(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.models.causal_lm import CausalLM

    return CausalLM(
        num_classes=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        heads_kv=cfg["num_key_value_heads"],
        mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.float32 if rehearse else jnp.bfloat16,
        **cfg.get("model_kwargs", {}))


def make_weights(model, seed: int, dtype):
    """The whole parameter tree on the device in one jitted call, in the
    type it is served in: never an f32 copy of the model.  The tree's
    shapes are the program's (``eval_shape`` of its ``init``); the values
    are the benchmark's: kernels normal at 1/sqrt(fan_in), biases normal
    at 0.02 (so that a dropped bias shows), LayerNorm scales 1, the
    embedding normal at 1/sqrt(dim) (tied logits of unit scale)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name == "scale":
                a = jnp.ones(s.shape, dtype)
            elif name == "bias":
                a = (0.02 * jax.random.normal(k, s.shape, jnp.float32)).astype(dtype)
            elif name == "embedding":
                a = (jax.random.normal(k, s.shape, jnp.float32)
                     * s.shape[-1] ** -0.5).astype(dtype)
            else:  # a matmul kernel, (fan_in, fan_out)
                a = (jax.random.normal(k, s.shape, jnp.float32)
                     * s.shape[0] ** -0.5).astype(dtype)
            out.append(a)
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def drive(engine, until, on_step=None) -> None:
    """Step the engine until ``until()`` is true."""
    while not until():
        if engine.has_work:
            with harness.annotate("engine.step"):
                engine.step()
            if on_step is not None:
                on_step()
        else:
            with harness.annotate("no_request"):  # names the device's idle gap
                while not engine.has_work and not until():
                    time.sleep(0.0005)


def check(engine, cell, vocab: int) -> dict:
    """Four requests through the engine against one plain-reference forward
    each: every emitted token near the reference's argmax, its reported
    log-probability near the reference's."""
    import jax

    rng = np.random.default_rng([cell.seed, 7])
    prompts = [rng.integers(1, vocab, n).astype(np.int32) for n in CHECK_PROMPTS]
    reqs = [engine.submit(p, max_new=CHECK_NEW) for p in prompts]
    drive(engine, lambda: all(r.status in ("done", "failed", "cancelled") for r in reqs))
    shape = reference.shape_of(cell.config, window=0)
    gap = err = 0.0
    ok = all(r.status == "done" and len(r.generated) == CHECK_NEW for r in reqs)
    for p, r in zip(prompts, reqs):
        if not ok:
            break
        g = np.asarray(r.generated, np.int32)
        row = np.zeros(CHECK_ROW, np.int32)
        row[:p.size + g.size] = np.concatenate([p, g])
        logits = np.asarray(reference.logits_one(engine.params, row, **shape))
        at = logits[p.size - 1:p.size - 1 + g.size]  # row t predicts token t+1
        picked = at[np.arange(g.size), g]
        gap = max(gap, float(np.max(at.max(-1) - picked)))
        logp = picked - np.asarray(jax.nn.logsumexp(at, axis=-1))
        err = max(err, float(np.max(np.abs(logp - np.asarray(r.logprobs)))))
    return {"ok": bool(ok and gap <= LOGIT_TOL and err <= LOGIT_TOL),
            "greedy_gap": gap, "logprob_err": err, "tolerance": LOGIT_TOL,
            "requests": len(reqs)}


def fill_pool(engine, cell, vocab: int, bucket: int, target: float, limit: int) -> dict:
    """Serve one-token requests with distinct prompts of the largest bucket
    until the radix trie's retained pages fill the pool to ``target``: the
    state a deployment is in after minutes of service, in which every
    admission evicts."""
    n = 0
    share = 0.0
    while n < limit:
        v = engine.stats.vitals()
        share = v["kv_pages_live"] / max(v["kv_pages_total"], 1)
        if share >= target:
            break
        batch = []
        for _ in range(min(8, limit - n)):
            toks = traffic_gen.prompt_tokens(cell.seed, (1 << 30) + n, bucket - 1, vocab)
            batch.append(engine.submit(toks, max_new=1))
            n += 1
        drive(engine, lambda: all(r.status not in ("queued", "running") for r in batch))
    return {"pool_fill_requests": n, "pool_fill_share": share}


def start_engine(cell: harness.Cell, setup: harness.Setup):
    """Everything before the client: weights, engine, ``prewarm()``, the
    correctness check and the filled pool.  Returns the engine and what
    each step reported."""
    import jax.numpy as jnp

    from distributed_tensorflow_ibm_mnist_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_ibm_mnist_tpu.serving.scheduler import FIFOScheduler

    if cell.rehearse:
        from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret

        set_interpret(True)
    cfg, tr = cell.config, cell.traffic
    opts = cfg["engine"]
    vocab = cfg["vocab_size"]
    buckets = tuple(tr.get("buckets") or opts["buckets"])
    if not set(buckets) <= set(opts["buckets"]):
        raise SystemExit(f"traffic buckets {buckets} are not among the "
                         f"configuration's {opts['buckets']}")
    model = build_model(cfg, cell.rehearse)
    params = make_weights(model, cell.jax_seed(),
                          jnp.float32 if cell.rehearse else jnp.bfloat16)
    setup.mark("weights")
    engine = InferenceEngine(
        model, params, slots=opts["slots"], max_len=opts["max_len"],
        scheduler=FIFOScheduler(max_len=opts["max_len"], buckets=buckets,
                                max_queue=opts["max_queue"]),
        decode_ahead=opts["decode_ahead"],
        kv_page_size=opts["kv_page_size"], kv_pages=opts["kv_pages"])
    warm = engine.prewarm()
    setup.mark("prewarm")
    chk = check(engine, cell, vocab)
    setup.mark("check")
    fill = fill_pool(engine, cell, vocab, max(buckets),
                     float(tr.get("pool_fill_target", 0.9)),
                     int(tr.get("pool_fill_limit", 400)))
    setup.mark("pool_fill")
    return engine, {"prewarm_programs": warm["programs"], "check": chk, "fill": fill}


def run(cell: harness.Cell, devs, setup: harness.Setup) -> dict:
    tr, vocab = cell.traffic, cell.config["vocab_size"]
    tracker = harness.compile_tracker()
    c0 = tracker.snapshot()
    setup.mark("import")
    engine, started = start_engine(cell, setup)
    chk, fill = started["check"], started["fill"]

    client = {"closed": ClosedLoop, "open": Replay}[tr["loop"]](engine, cell, vocab)
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
        "check": {**chk, **fill, **out.get("notes", {})},
        "setup": {**{k: round(v, 3) for k, v in setup.items.items()},
                  "setup_s": round(setup.total(), 3), **built},
        "profiler": profiler,
    }


class Client:
    """What both loops share: submitting with a timestamping callback, the
    per-step samples, and the trace's own shorter window."""

    def __init__(self, engine, cell, vocab):
        self.engine, self.cell, self.vocab = engine, cell, vocab
        self.tr = cell.traffic
        self.recs: list[Rec] = []
        self.pool_samples: list[float] = []

    def submit(self, rec: Rec, prompt_len: int) -> None:
        toks = traffic_gen.prompt_tokens(self.cell.seed, rec.index, int(prompt_len),
                                         self.vocab, self.tr)
        rec.sent = time.perf_counter()
        rec.req = self.engine.submit(
            toks, max_new=int(rec.want),
            callback=lambda _r, _t, rec=rec: rec.times.append(time.perf_counter()))
        self.recs.append(rec)

    def sample(self) -> None:
        v = self.engine.stats.vitals()
        self.pool_samples.append(v["kv_pages_live"] / max(v["kv_pages_total"], 1))

    def seconds(self) -> float:
        """The window's length: ``--seconds``, or in a traced run the
        trace's own shorter window, so that writing the trace out (seconds
        of a blocked host) never falls inside what the counters cover."""
        if self.cell.trace:
            return min(float(self.tr.get("trace_seconds", 10.0)), self.cell.seconds)
        return self.cell.seconds

    def run_window(self, profiler, t0: float, t1: float, on_step) -> dict:
        """Drive the engine from ``t0`` to ``t1`` under the profiler (when
        it is on); returns the program's own counters over the window."""
        s0 = self.engine.stats.summary()
        mono0 = time.monotonic()
        profiler.start()

        def step():
            on_step()
            self.sample()

        drive(self.engine, lambda: time.perf_counter() >= t1, step)
        mono1 = time.monotonic()
        profiler.stop()
        s1 = self.engine.stats.summary()
        windows = s1["n_windows"] - s0["n_windows"]
        steps = s1["window_steps"] - s0["window_steps"]
        counters = {
            "decode_batch_mean": (steps / windows / self.engine.decode_ahead
                                  if windows else None),
            "kv_pool_fill_share": (100.0 * float(np.mean(self.pool_samples))
                                   if self.pool_samples else None),
            "radix_hits": s1["radix_hits"] - s0["radix_hits"],
        }
        if profiler.on:
            # real prompt tokens whose prefill ran under the trace
            counters["traced_prompt_tokens"] = int(sum(
                r.req.tokens.size for r in self.recs
                if r.req is not None and r.req.admit_t is not None
                and mono0 <= r.req.admit_t < mono1))
        return counters

    def failed(self, recs) -> int:
        return sum(1 for r in recs if r.req is None
                   or r.req.status in ("failed", "cancelled"))


class ClosedLoop(Client):
    """As many clients as the traffic says, each sending its next request
    when its last completes.  First answers are cut to random lengths so
    that clients do not retire in waves."""

    PER_CLIENT = 256

    def __init__(self, engine, cell, vocab):
        super().__init__(engine, cell, vocab)
        self.tab = traffic_gen.closed_tables(self.tr, self.PER_CLIENT)
        self.n_clients = int(self.tr["clients"])
        self.next_k = [0] * self.n_clients
        self.live: dict[int, Rec] = {}
        self.completed = 0

    def send(self, c: int) -> None:
        k = self.next_k[c] % self.PER_CLIENT
        self.next_k[c] += 1
        rec = Rec(c * self.PER_CLIENT + k, None, int(self.tab["max_new"][c, k]))
        self.submit(rec, self.tab["prompt_len"][c, k])
        self.live[c] = rec

    def refill(self) -> None:
        for c, rec in list(self.live.items()):
            if rec.done or rec.req.status in ("failed", "cancelled"):
                self.completed += 1
                self.send(c)

    def warm_in(self) -> None:
        for c in range(self.n_clients):
            self.send(c)
        want = int(self.tr.get("warm_in_completions", self.n_clients // 2))
        drive(self.engine, lambda: self.completed >= want, self.refill)

    def window(self, profiler) -> dict:
        first = len(self.recs)
        live_at_start = list(self.live.values())
        t0 = time.perf_counter()
        t1 = t0 + self.seconds()
        counters = self.run_window(profiler, t0, t1, self.refill)
        recs = live_at_start + self.recs[first:]
        tokens = sum(1 for r in recs for t in r.times if t0 <= t < t1)
        return {
            "attempted": len(recs), "failed": self.failed(recs),
            "end_to_end": {"out_tok_per_s": tokens / (t1 - t0)},
            "counters": counters,
            "notes": {"window_tokens": tokens, "window_requests": len(recs)},
        }


class Replay(Client):
    """The open loop: the traffic file's trace replayed on the clock by a
    generator thread, whatever the engine does.  Time to first token is
    taken from when a request was due."""

    COOL_S = 20.0  # the trace goes on past the window until its requests finish

    def __init__(self, engine, cell, vocab):
        super().__init__(engine, cell, vocab)
        self.warm_s = float(self.tr.get("warm_in_s", 5.0))
        horizon = self.warm_s + cell.seconds + self.COOL_S
        self.sched = traffic_gen.open_schedule(self.tr, horizon)
        self.stop = threading.Event()
        self.thread = None
        self.t_start = None
        self.shed = 0
        self.error = None

    def generate(self) -> None:
        try:
            self._generate()
        except BaseException as e:  # the window's thread reports it
            self.error = e
            raise

    def _generate(self) -> None:
        from distributed_tensorflow_ibm_mnist_tpu.serving.scheduler import QueueFull

        due, pl, mn = self.sched["due"], self.sched["prompt_len"], self.sched["max_new"]
        for i in range(len(due)):
            at = self.t_start + float(due[i])
            while not self.stop.is_set():
                wait = at - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            if self.stop.is_set():
                return
            rec = Rec(i, at, int(mn[i]))
            try:
                with harness.annotate("submit"):
                    self.submit(rec, pl[i])
            except QueueFull:  # shed at the queue's bound: a failed request
                rec.req = None
                self.recs.append(rec)
                self.shed += 1

    def warm_in(self) -> None:
        self.t_start = time.perf_counter()
        self.thread = threading.Thread(target=self.generate, daemon=True)
        self.thread.start()
        t_open = self.t_start + self.warm_s
        drive(self.engine, lambda: time.perf_counter() >= t_open)

    def window(self, profiler) -> dict:
        t0 = self.t_start + self.warm_s
        t1 = t0 + self.seconds()

        def mine():
            return [r for r in list(self.recs) if t0 <= r.due < t1]

        try:
            counters = self.run_window(profiler, t0, t1, lambda: None)
            # from now, not from t1: writing a trace out can take as long
            t_give_up = time.perf_counter() + self.COOL_S
            drive(self.engine, lambda: time.perf_counter() >= t_give_up or all(
                r.req is None or r.done or r.req.status in ("failed", "cancelled")
                for r in mine()))
        finally:
            self.stop.set()
            self.thread.join()
        if self.error is not None:
            raise RuntimeError("the traffic generator's thread failed") from self.error
        recs = mine()
        served = [r for r in recs if r.req is not None and r.times]
        ttft = [r.times[0] - r.due for r in served]
        gaps = [b - a for r in self.recs for a, b in zip(r.times, r.times[1:])
                if t0 <= b < t1]
        late = [r.sent - r.due for r in recs if r.sent is not None]
        waits = [r.req.admit_t - r.req.submit_t for r in served
                 if r.req.admit_t is not None]
        unfinished = sum(1 for r in recs if r.req is not None and not r.done
                         and r.req.status not in ("failed", "cancelled"))
        counters.update({
            "queue_wait_p50_s": harness.percentile(waits, 50),
            "gen_late_p90_s": harness.percentile(late, 90),
            "ttft_p50_s": harness.percentile(ttft, 50),
        })
        # the backlog at the window's middle and end: the sweep's knee rule
        def backlog(at):
            return sum(1 for r in self.recs if r.due <= at
                       and (not r.times or len(r.times) < r.want or r.times[-1] > at))
        return {
            "attempted": len(recs), "failed": self.failed(recs) + unfinished,
            "end_to_end": {"ttft_p90_s": harness.percentile(ttft, 90),
                           "itl_p95_s": harness.percentile(gaps, 95)},
            "counters": counters,
            "notes": {"window_requests": len(recs), "itl_samples": len(gaps),
                      "unfinished": unfinished, "shed": self.shed,
                      "not_ok": [
                          {"index": r.index, "want": r.want, "got": len(r.times),
                           "status": getattr(r.req, "status", "shed"),
                           "error": getattr(r.req, "error", None)}
                          for r in recs if r.req is None or not r.done
                          or r.req.status != "done"][:5],
                      "ttft_p50_s": harness.percentile(ttft, 50),
                      "itl_p50_s": harness.percentile(gaps, 50),
                      "completed_per_s": sum(
                          1 for r in self.recs if r.done and t0 <= r.times[-1] < t1
                      ) / (t1 - t0),
                      "offered_per_s": len(recs) / (t1 - t0),
                      "backlog_mid": backlog((t0 + t1) / 2), "backlog_end": backlog(t1),
                      "decode_batch_mean": counters["decode_batch_mean"]},
        }
