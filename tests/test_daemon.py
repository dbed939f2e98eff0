"""Daemonized serving tier (serving/daemon.py, serving/policies.py).

The decisive properties (ISSUE 15):

* PARITY + LIFECYCLE — tokens through the daemon's thread stack (pumps,
  dispatcher, delivery) are identical to one fault-free engine; a clean
  ``drain()`` + ``close()`` leaves ``tracer.open_spans == 0`` and every
  KV pool at refcount zero.
* CONSERVATION under concurrency — N producer threads hammering
  ``submit()`` against a small ``max_queue`` with deadline lapses mixed
  in: submitted == done + cancelled + failed exactly, rejections raised
  at submit and never counted as submitted, and every request's stream
  (callback order, ``stream()`` order, ``tokens``) is its final answer
  in order, exactly once.
* FAILOVER — a pump killed (``daemon-pump`` raise) or wedged
  (``daemon-pump`` wedge + the watchdog's external liveness check) mid
  wave: zero drops, exactly-once streams, token parity.
* CHAOS DETERMINISM — the same ``FaultPlan`` run twice against the
  daemonized tier (threads and all) fires at identical per-site event
  indices and yields token-identical non-poisoned outputs.
* POLICIES — priority classes drain high-before-low; the deadline
  policy admits everything cold, sheds ``SLOUnmeetable`` once its EMA
  says the TTFT SLO is unmeetable.
* THREAD-SAFE TELEMETRY — ServingStats / MetricsRegistry / Telemetry
  hammered from many threads lose no increments and never tear.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    DaemonRequest,
    DeadlineAwarePolicy,
    FIFOScheduler,
    InferenceEngine,
    PriorityPolicy,
    QueueFull,
    Router,
    ServingDaemon,
    ServingStats,
    SLOUnmeetable,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.telemetry import (
    MetricsRegistry,
    Telemetry,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import Tracer

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [9, 1], [3, 3, 3, 3]]

WAIT_S = 120.0   # per-request terminal wait: generous, never load-bearing


@pytest.fixture(scope="module")
def model_and_params():
    model = get_model("causal_lm", **KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _factory(model, params, **kw):
    def make_engine(tid):
        return InferenceEngine(
            model, params, slots=2, max_len=16,
            scheduler=FIFOScheduler(max_len=16, buckets=(8,), max_queue=16),
            trace_tid=tid, **kw)
    return make_engine


def _reference(model, params, prompts=PROMPTS, max_new=6):
    eng = InferenceEngine(model, params, slots=2, max_len=16,
                          scheduler=FIFOScheduler(max_len=16, buckets=(8,)))
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run()
    eng.close()
    return [list(r.generated) for r in reqs]


def _drain_stream(daemon, dr):
    """Consume dr's event queue after the fact (terminal already set):
    the token order stream() would have yielded live."""
    out = []
    for tok in daemon.stream(dr, timeout=5.0):
        out.append(tok)
    return out


# ----------------------------------------------------------------------
# parity + lifecycle


def test_daemon_parity_streams_and_clean_drain(model_and_params,
                                               pools_refcount_zero):
    """Greedy decode through the full thread stack == one fault-free
    engine; callbacks/stream()/tokens agree; drain leaves open_spans == 0
    and the paged KV pools at refcount zero; conservation exact."""
    model, params = model_and_params
    want = _reference(model, params)
    tracer = Tracer()
    router = Router(_factory(model, params, kv_page_size=4), 2,
                    tracer=tracer)
    d = ServingDaemon(router, liveness_timeout_s=60.0)
    cb_order: dict[int, list[int]] = {}
    with d:
        drs = []
        for p in PROMPTS:
            got: list[int] = []
            dr = d.submit(p, 6,
                          callback=lambda dr, tok, got=got: got.append(tok))
            cb_order[dr.id] = got
            drs.append(dr)
        assert all(dr.wait(WAIT_S) for dr in drs)
        assert [dr.tokens for dr in drs] == want
        assert all(dr.status == "done" and dr.error is None for dr in drs)
        # exactly-once, in order, on every surface: delivery callback,
        # the stream() event feed, and the router's own generated list
        assert [cb_order[dr.id] for dr in drs] == want
        assert [_drain_stream(d, dr) for dr in drs] == want
        assert [list(dr.rr.generated) for dr in drs] == want
        cons = d.conservation()
        assert cons["conserved"]
        assert cons["submitted"] == cons["done"] == len(PROMPTS)
        assert cons["outstanding"] == cons["rejected"] == 0
        assert d.drain(timeout=60.0)
        # drained tier: admission refused, nothing left in flight
        with pytest.raises(RuntimeError):
            d.submit([1, 2], 2)
        assert pools_refcount_zero(router)
    assert tracer.open_spans == 0
    with pytest.raises(RuntimeError):
        d.submit([1, 2], 2)
    d.close()   # idempotent


def test_daemon_close_cancels_queued_work(model_and_params):
    """close() without a drain settles every queued request: terminal
    ``cancelled``, end event delivered, conservation still exact."""
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    d = ServingDaemon(router, max_queue=4)   # never started: all queued
    drs = [d.submit(p, 4) for p in PROMPTS[:3]]
    d.close()
    assert all(dr.wait(5.0) for dr in drs)
    assert all(dr.status == "cancelled" for dr in drs)
    cons = d.conservation()
    assert cons["conserved"]
    assert cons["submitted"] == cons["cancelled"] == 3


# ----------------------------------------------------------------------
# backpressure + policies


def test_daemon_queue_full_at_admission_bound(model_and_params):
    """The admission bound is decided atomically at submit: the caller
    over the bound gets QueueFull, counted rejected, never submitted."""
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    d = ServingDaemon(router, max_queue=2)   # not started: queue only fills
    d.submit([1, 2], 2)
    d.submit([3, 4], 2)
    with pytest.raises(QueueFull):
        d.submit([5, 6], 2)
    cons = d.conservation()
    assert cons["rejected"] == 1 and cons["submitted"] == 2
    d.close()
    assert d.conservation()["conserved"]


def test_priority_policy_drains_high_before_low(model_and_params):
    """Requests heaped before start dispatch strictly high-priority
    first, FIFO within a class — visible in router submit order."""
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    d = ServingDaemon(router, policy=PriorityPolicy())
    prios = [0, 5, 1, 5, 0, 3]
    drs = [d.submit(p, 4, priority=pr) for p, pr in zip(PROMPTS, prios)]
    d.start()
    assert all(dr.wait(WAIT_S) for dr in drs)
    assert all(dr.status == "done" for dr in drs)
    # router.requests is dispatch order; map each back to its daemon
    # request via the rr handle
    by_rr = {id(dr.rr): dr for dr in drs}
    dispatched = [by_rr[id(rr)] for rr in router.requests]
    want = sorted(drs, key=lambda dr: (-dr.priority, dr.id))
    assert [dr.id for dr in dispatched] == [dr.id for dr in want]
    d.close()


def test_deadline_policy_predicts_and_sheds():
    """Unit math: cold start admits everything; after feedback the EMA
    predicts queue wait and sheds unmeetable TTFT SLOs as SLOUnmeetable
    (a QueueFull subclass — existing backpressure handlers shed it)."""
    pol = DeadlineAwarePolicy(alpha=0.5, concurrency=2, slack=1.0)

    def req(rid, ttft):
        return DaemonRequest(rid, [1], 1, deadline_s=None, submit_t=0.0,
                             callback=None, ttft_slo_s=ttft)

    assert pol.predicted_wait_s(10) is None
    pol.admit(req(0, 0.001), queued=100)      # cold: no basis to shed
    pol.note_first_token(0.4)
    assert pol.ema_wait_s == pytest.approx(0.4)
    pol.note_first_token(0.2)                 # EMA folds feedback in
    assert pol.ema_wait_s == pytest.approx(0.3)
    assert pol.predicted_wait_s(4) == pytest.approx(0.3 * (1 + 4 / 2))
    pol.admit(req(1, 1.0), queued=4)          # 0.9 predicted <= 1.0 SLO
    with pytest.raises(SLOUnmeetable):
        pol.admit(req(2, 0.5), queued=4)      # 0.9 predicted > 0.5 SLO
    pol.admit(req(3, None), queued=4)         # no TTFT SLO: never shed
    assert pol.shed == 1 and pol.observations == 2
    assert isinstance(SLOUnmeetable("x"), QueueFull)


def test_daemon_counts_policy_shed_as_rejected(model_and_params):
    """A policy shed at submit() surfaces to the caller and lands in the
    rejected counter — never in submitted (conservation's outer edge)."""
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    pol = DeadlineAwarePolicy(concurrency=1)
    pol.note_first_token(1.0)                 # trained: predicts 1s wait
    d = ServingDaemon(router, policy=pol)
    with pytest.raises(SLOUnmeetable):
        d.submit([1, 2], 2, ttft_slo_s=0.01)
    dr = d.submit([1, 2], 2)                  # no SLO: sails through
    cons = d.conservation()
    assert cons["rejected"] == 1 and cons["submitted"] == 1
    assert dr.status == "queued"
    d.close()


# ----------------------------------------------------------------------
# concurrent submit hammer (satellite: conservation under threads)


def test_concurrent_submit_hammer_conserves_and_orders(model_and_params):
    """N producer threads against a small admission bound with deadline
    lapses mixed in: every submit is accounted exactly once (submitted ==
    done + cancelled + failed; rejections raised at the caller), and
    every request's delivered stream is its final token list, in order."""
    model, params = model_and_params
    router = Router(_factory(model, params), 2)
    d = ServingDaemon(router, max_queue=8, liveness_timeout_s=60.0)
    d.start()
    n_threads, per_thread = 4, 10
    drs_lock = threading.Lock()
    drs: list = []
    rejected = [0] * n_threads

    def producer(t):
        for i in range(per_thread):
            # every 5th submit is born overdue -> cancelled in dispatch
            deadline = 0.0 if i % 5 == 4 else None
            try:
                dr = d.submit(PROMPTS[(t + i) % len(PROMPTS)], 3,
                              deadline_s=deadline)
            except QueueFull:
                rejected[t] += 1
                continue
            with drs_lock:
                drs.append(dr)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(dr.wait(WAIT_S) for dr in drs)
    assert d.drain(timeout=60.0)
    cons = d.conservation()
    d.close()

    assert cons["conserved"]
    assert cons["submitted"] == len(drs)
    assert cons["rejected"] == sum(rejected)
    assert cons["submitted"] + cons["rejected"] == n_threads * per_thread
    by_status: dict[str, int] = {}
    for dr in drs:
        by_status[dr.status] = by_status.get(dr.status, 0) + 1
    assert by_status.get("done", 0) == cons["done"] > 0
    assert by_status.get("cancelled", 0) == cons["cancelled"]
    assert by_status.get("failed", 0) == cons["failed"] == 0
    # per-request order and exactly-once: the delivered stream IS the
    # final token list, and matches the router's record where dispatched
    for dr in drs:
        assert _drain_stream(d, dr) == dr.tokens
        if dr.status == "done":
            assert dr.tokens == list(dr.rr.generated)
            assert len(dr.tokens) == 3
        elif dr.rr is None:
            assert dr.tokens == []


# ----------------------------------------------------------------------
# failover: pump killed, pump wedged


def test_pump_kill_failover_zero_drops_exactly_once(model_and_params):
    """daemon-pump chaos kills one of two pumps mid-wave: the survivor
    absorbs the harvest, every request still retires done with reference
    tokens, streams stay exactly-once, conservation exact."""
    model, params = model_and_params
    want = _reference(model, params)
    inj = FaultInjector(FaultPlan(seed=3, faults=(
        FaultSpec(site="daemon-pump", kind="raise", at=(0,)),)))
    router = Router(_factory(model, params), 2, chaos=inj)
    d = ServingDaemon(router, liveness_timeout_s=60.0)
    drs = [d.submit(p, 6) for p in PROMPTS]   # work waiting before pumps
    d.start()
    assert all(dr.wait(WAIT_S) for dr in drs)
    assert all(dr.status == "done" for dr in drs)        # zero drops
    assert [dr.tokens for dr in drs] == want             # parity
    assert [list(dr.rr.generated) for dr in drs] == want  # exactly-once
    assert router.failovers == 1
    assert d.counters["pump_faults"] == 1
    assert [(f.site, f.event, f.kind) for f in inj.fired] == [
        ("daemon-pump", 0, "raise")]
    assert d.drain(timeout=60.0)
    cons = d.conservation()
    d.close()
    assert cons["conserved"] and cons["done"] == len(PROMPTS)


def test_pump_wedge_watchdog_failover(model_and_params):
    """daemon-pump kind="wedge" parks a pump with its heartbeat frozen —
    ``step()`` never raises, so only the watchdog's EXTERNAL liveness
    check can notice.  It must fail the replica over and the survivor
    must finish the wave with zero drops."""
    model, params = model_and_params
    want = _reference(model, params)
    inj = FaultInjector(FaultPlan(seed=4, faults=(
        FaultSpec(site="daemon-pump", kind="wedge", at=(0,)),)))
    tracer = Tracer()
    router = Router(_factory(model, params), 2, chaos=inj, tracer=tracer)
    router.prewarm()   # compiles out of the liveness window
    d = ServingDaemon(router, liveness_timeout_s=1.5,
                      watchdog_interval_s=0.05)
    drs = [d.submit(p, 6) for p in PROMPTS]
    d.start()
    assert all(dr.wait(WAIT_S) for dr in drs)
    assert all(dr.status == "done" for dr in drs)
    assert [dr.tokens for dr in drs] == want
    assert router.failovers == 1
    assert d.counters["pump_wedges"] == 1
    wedged = [f for f in inj.fired if f.site == "daemon-pump"]
    assert [(f.event, f.kind) for f in wedged] == [(0, "wedge")]
    assert d.drain(timeout=60.0)
    cons = d.conservation()
    d.close()
    assert cons["conserved"] and cons["done"] == len(PROMPTS)
    assert tracer.open_spans == 0


# ----------------------------------------------------------------------
# chaos determinism under threads (ISSUE 15 acceptance)


def _determinism_run(model, params, n_replicas, plan):
    """One daemonized run under ``plan`` with all work submitted before
    the threads start; returns the chaos fired log and every request's
    terminal (status, tokens)."""
    inj = FaultInjector(plan)
    router = Router(_factory(model, params, chaos=inj), n_replicas,
                    chaos=inj)
    d = ServingDaemon(router, liveness_timeout_s=60.0)
    drs = [d.submit(p, 6) for p in PROMPTS]
    d.start()
    assert all(dr.wait(WAIT_S) for dr in drs)
    assert d.drain(timeout=60.0)
    d.close()
    fired = [(f.site, f.event, f.kind, f.spec_idx) for f in inj.fired]
    outputs = [(dr.status, tuple(dr.tokens)) for dr in drs]
    return fired, outputs, inj.events("daemon-pump")


def test_chaos_determinism_repeated_run(model_and_params):
    """The replayability pin: the same FaultPlan run twice against the
    daemonized tier — pump/dispatcher/delivery threads interleaving
    freely — fires at identical per-site event indices and yields
    token-identical non-poisoned outputs."""
    model, params = model_and_params

    # (a) single replica, a poisoned admission mid-wave: the per-site
    # FIFO admission order pins exactly WHICH request dies
    plan = FaultPlan(seed=7, faults=(
        FaultSpec(site="serving-admit", kind="raise", at=(2,)),))
    fired1, out1, pump_events1 = _determinism_run(model, params, 1, plan)
    fired2, out2, pump_events2 = _determinism_run(model, params, 1, plan)
    assert fired1 == fired2 == [("serving-admit", 2, "raise", 0)]
    assert out1 == out2
    assert pump_events1 == pump_events2 == 1   # one pump, consulted once
    statuses = [s for s, _ in out1]
    assert statuses.count("failed") == 1 and statuses.count("done") == 5
    assert out1[2][0] == "failed"              # admission order == submit

    # (b) two replicas, a pump killed: WHICH pump loses the race for
    # event 0 is scheduling-dependent, but the per-site event log and
    # the token outputs are interleaving-invariant
    plan = FaultPlan(seed=8, faults=(
        FaultSpec(site="daemon-pump", kind="raise", at=(0,)),))
    fired1, out1, _ = _determinism_run(model, params, 2, plan)
    fired2, out2, _ = _determinism_run(model, params, 2, plan)
    assert fired1 == fired2 == [("daemon-pump", 0, "raise", 0)]
    assert out1 == out2
    assert all(s == "done" for s, _ in out1)


# ----------------------------------------------------------------------
# thread-safe stats/telemetry (satellite: no torn counters)


def test_serving_stats_concurrent_hammer_exact_counts():
    """Many threads mutating one ServingStats while merge/summary run
    concurrently: no increment lost, no exception, merged counters sum
    exactly (the pre-lock implementation tore under this load)."""
    a, b = ServingStats(slots=2), ServingStats(slots=2)
    n_threads, iters = 8, 300
    stop = threading.Event()
    reader_errors: list = []

    def mutate(rec):
        for i in range(iters):
            rec.tick(occupied=1, dt=0.001, decoded=True)
            rec.prefix(hit=i % 2 == 0)
            rec.spec(drafted=2, accepted=1)

    def read():
        while not stop.is_set():
            try:
                a.summary()
                ServingStats.merge([a, b])
            except Exception as e:   # pragma: no cover - the regression
                reader_errors.append(e)
                return

    threads = ([threading.Thread(target=mutate, args=(a,))
                for _ in range(n_threads // 2)]
               + [threading.Thread(target=mutate, args=(b,))
                  for _ in range(n_threads // 2)]
               + [threading.Thread(target=read) for _ in range(2)])
    for t in threads:
        t.start()
    for t in threads[:n_threads]:
        t.join()
    stop.set()
    for t in threads[n_threads:]:
        t.join()
    assert not reader_errors
    per_rec = (n_threads // 2) * iters
    for rec in (a, b):
        s = rec.summary()
        assert s["decode_steps"] == per_rec
        assert s["prefix_hits"] + s["prefix_misses"] == per_rec
        assert s["drafted_tokens"] == 2 * per_rec
        assert s["accepted_tokens"] == per_rec
    merged = ServingStats.merge([a, b])
    assert merged["decode_steps"] == 2 * per_rec


def test_metrics_registry_concurrent_inc_is_exact():
    """Parallel inc/observe/snapshot: the counter lands on exactly
    n_threads * iters — a single lost update fails this."""
    reg = MetricsRegistry()
    n_threads, iters = 8, 500

    def work():
        for i in range(iters):
            reg.inc("hits")
            reg.observe("lat", 0.001 * (i % 7 + 1))
            reg.set_gauge("depth", i)

    readers_stop = threading.Event()

    def read():
        while not readers_stop.is_set():
            reg.snapshot()
            reg.to_prometheus()

    threads = ([threading.Thread(target=work) for _ in range(n_threads)]
               + [threading.Thread(target=read)])
    for t in threads:
        t.start()
    for t in threads[:n_threads]:
        t.join()
    readers_stop.set()
    threads[-1].join()
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == n_threads * iters


def test_telemetry_maybe_sample_once_per_interval():
    """Concurrent maybe_sample() calls racing one interval boundary:
    exactly ONE caller samples (the double-checked lock), the rest see
    None — no duplicate samples, no torn sample count."""
    t = [0.0]
    tel = Telemetry(interval_s=1.0, clock=lambda: t[0])
    tel.register_source("x", lambda: {"v": 1})
    for tick in (0.0, 10.0, 20.0):
        t[0] = tick
        barrier = threading.Barrier(8)
        results: list = []
        res_lock = threading.Lock()

        def call():
            barrier.wait()
            r = tel.maybe_sample()
            with res_lock:
                results.append(r)

        threads = [threading.Thread(target=call) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert sum(r is not None for r in results) == 1
    assert tel.samples == 3
    tel.close()
