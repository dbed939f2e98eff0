"""The internet-shaped front door (serving/frontend.py) + request
cancellation (ISSUE 17).

The decisive properties:

* WIRE PARITY — tokens served over HTTP (unary JSON and SSE stream) are
  identical to :meth:`ServingDaemon.stream` for the same prompts and
  seeds, greedy AND sampled: the protocol layer adds transport, never
  content.
* DISCONNECT CANCELS — a client hanging up mid-SSE-stream cancels the
  underlying request: the slot frees, the KV pool returns to refcount
  zero, the tracer drains to ``open_spans == 0``, and conservation stays
  EXACT with the request counted ``cancelled`` — a vanished client costs
  the tier nothing.
* BACKPRESSURE ON THE WIRE — the daemon's ``QueueFull`` surfaces as 429
  and ``SLOUnmeetable``/draining as 503, carrying the admission policy's
  wait-predictor hint as a real ``Retry-After`` header plus a
  machine-readable ``retry_after_s`` body field
  (``rejected_with_hint`` counts them daemon-side).
* PROTOCOL EDGES — validation 400s name the offending field; unknown
  paths 404; wrong methods 405; ``/healthz`` exposes the replica census
  + conservation; ``/metrics`` serves the shared Prometheus registry
  with the frontend's own counters in the same scrape.
"""

import json
import socket
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    DeadlineAwarePolicy,
    FIFOScheduler,
    FrontDoor,
    FrontDoorClient,
    InferenceEngine,
    Router,
    SamplingParams,
    ServingDaemon,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.frontend import (
    _parse_generate,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.telemetry import (
    MetricsRegistry,
    Telemetry,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
    TraceContext,
    Tracer,
)

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)
PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6]]
WAIT_S = 120.0


@pytest.fixture(scope="module")
def model_and_params():
    model = get_model("causal_lm", **KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _factory(model, params, **kw):
    def make_engine(tid):
        return InferenceEngine(
            model, params, slots=2, max_len=16, kv_page_size=4,
            scheduler=FIFOScheduler(max_len=16, buckets=(8,), max_queue=16),
            trace_tid=tid, **kw)
    return make_engine


@pytest.fixture()
def tier(model_and_params):
    """A 2-replica daemon + front door on an ephemeral port, torn down
    hard so a failing test never leaks the listener thread."""
    model, params = model_and_params
    tracer = Tracer()
    router = Router(_factory(model, params, tracer=tracer), 2,
                    tracer=tracer)
    daemon = ServingDaemon(router, max_queue=32).start()
    fd = FrontDoor(daemon).start_in_thread()
    try:
        yield daemon, fd, tracer
    finally:
        fd.stop()
        if not daemon._closed:
            daemon.drain(timeout=30.0)
            daemon.close()


# ----------------------------------------------------------------------
# request validation (no tier needed)


def test_parse_generate_validation():
    ok = _parse_generate({"prompt": [1, 2], "max_new": 3})
    assert ok["prompt"] == [1, 2] and ok["max_new"] == 3
    assert ok["stream"] is False and ok["sampling"] is None
    spec = _parse_generate({"prompt": [1], "max_new": 1, "stream": True,
                            "priority": 2, "deadline_s": 5,
                            "sampling": {"temperature": 0.5, "seed": 7}})
    assert spec["stream"] is True and spec["priority"] == 2
    assert spec["deadline_s"] == 5.0
    assert spec["sampling"] == SamplingParams(temperature=0.5, seed=7)
    for bad in (
            [],                                        # not an object
            {"max_new": 2},                            # no prompt
            {"prompt": [], "max_new": 2},              # empty prompt
            {"prompt": [1.5], "max_new": 2},           # non-int token
            {"prompt": [True], "max_new": 2},          # bool is not a token
            {"prompt": [1], "max_new": 0},             # max_new < 1
            {"prompt": [1], "max_new": 2, "deadline_s": -1},
            {"prompt": [1], "max_new": 2, "priority": "high"},
            {"prompt": [1], "max_new": 2, "sampling": {"beam": 4}},
            {"prompt": [1], "max_new": 2,
             "sampling": {"temperature": 0.0, "top_p": 0.5}},  # greedy+top_p
    ):
        with pytest.raises(ValueError):
            _parse_generate(bad)


# ----------------------------------------------------------------------
# wire parity


def test_http_parity_unary_stream_greedy_and_sampled(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    sampled = {"temperature": 0.7, "top_k": 5, "seed": 42}
    for prompt in PROMPTS:
        for sampling in (None, sampled):
            kw = {} if sampling is None else {"sampling": sampling}
            unary = cli.generate(prompt, 4, **kw)
            assert cli.last_status == 200, unary
            sse = list(cli.stream(prompt, 4, **kw))
            assert cli.last_terminal["status"] == "done"
            assert cli.last_terminal["n_tokens"] == len(sse)
            dr = daemon.submit(
                prompt, 4,
                sampling=None if sampling is None
                else SamplingParams(**sampling))
            ref = list(daemon.stream(dr))
            assert dr.status == "done"
            # the three transports agree token-for-token
            assert unary["tokens"] == sse == ref, (prompt, sampling)


def test_stream_order_matches_delivery(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    streams = {}
    lock = threading.Lock()

    def worker(i, prompt):
        toks = list(cli_for[i].stream(prompt, 4))
        with lock:
            streams[i] = (toks, cli_for[i].last_terminal)

    cli_for = {i: FrontDoorClient("127.0.0.1", fd.port)
               for i in range(len(PROMPTS))}
    threads = [threading.Thread(target=worker, args=(i, p))
               for i, p in enumerate(PROMPTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=WAIT_S)
    assert len(streams) == len(PROMPTS)
    for i, prompt in enumerate(PROMPTS):
        toks, terminal = streams[i]
        assert terminal["status"] == "done"
        dr = daemon.submit(prompt, 4)
        assert list(daemon.stream(dr)) == toks


# ----------------------------------------------------------------------
# disconnect cancels (ISSUE 17 satellite: slot + pages freed, spans
# closed, conservation exact)


def test_client_disconnect_mid_stream_cancels(tier, pools_refcount_zero):
    daemon, fd, tracer = tier
    body = json.dumps({"prompt": [5, 6, 7], "max_new": 6, "stream": True,
                       "deadline_s": 60.0}).encode()
    sock = socket.create_connection(("127.0.0.1", fd.port), timeout=30)
    sock.sendall(
        b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
        b"Content-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    sock.recv(64)          # the stream started (headers on the wire)
    sock.close()           # client vanishes mid-stream
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline and fd.counters["disconnect_cancels"] < 1:
        time.sleep(0.02)
    assert fd.counters["disconnects"] >= 1
    assert fd.counters["disconnect_cancels"] == 1
    # the cancel must settle the request: nothing outstanding, counted
    # cancelled (or done, if the hangup raced the final token), books exact
    while time.monotonic() < deadline:
        cons = daemon.conservation()
        if cons["outstanding"] == 0:
            break
        time.sleep(0.02)
    assert cons["outstanding"] == 0 and cons["conserved"]
    assert cons["cancelled"] + cons["done"] == cons["submitted"]
    assert daemon.drain(timeout=30.0)
    # slot free, pages free, spans closed — the disconnect leaked nothing
    for rep in daemon.router.replicas:
        assert rep.engine.occupied == 0
    assert pools_refcount_zero(daemon.router)
    assert tracer.open_spans == 0


def test_disconnect_before_first_token_cancels_queued(tier):
    daemon, fd, _tracer = tier
    # wedge the admission path: fill both replicas' slots with real work
    # so the victim waits QUEUED when its client hangs up
    background = [daemon.submit(p, 6) for p in PROMPTS]
    body = json.dumps({"prompt": [9, 9], "max_new": 4,
                       "stream": True}).encode()
    sock = socket.create_connection(("127.0.0.1", fd.port), timeout=30)
    sock.sendall(
        b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
        b"Content-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    sock.close()           # gone before reading a byte
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline and fd.counters["disconnects"] < 1:
        time.sleep(0.02)
    assert fd.counters["disconnects"] >= 1
    for dr in background:
        assert dr.wait(timeout=WAIT_S) and dr.status == "done"
    while time.monotonic() < deadline:
        cons = daemon.conservation()
        if cons["outstanding"] == 0:
            break
        time.sleep(0.02)
    assert cons["conserved"] and cons["outstanding"] == 0


# ----------------------------------------------------------------------
# daemon.cancel() — the API under the disconnect path


def test_daemon_cancel_queued_and_inflight(model_and_params):
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    daemon = ServingDaemon(router, max_queue=32).start()
    try:
        # in-flight: cancel while decoding
        first = daemon.submit([1, 2, 3], 6)
        victims = [daemon.submit(p, 6) for p in PROMPTS]
        doomed = victims[-1]
        assert daemon.cancel(doomed)
        assert doomed.wait(timeout=WAIT_S)
        assert doomed.status == "cancelled"
        for dr in [first] + victims[:-1]:
            assert dr.wait(timeout=WAIT_S) and dr.status == "done"
        # terminal request: cancel is a no-op, not an error
        assert daemon.cancel(first) is False
        cons = daemon.conservation()
        assert cons["conserved"] and cons["cancelled"] >= 1
        assert daemon.drain(timeout=30.0)
    finally:
        daemon.close()


# ----------------------------------------------------------------------
# backpressure on the wire


def test_429_carries_policy_retry_after(model_and_params):
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    policy = DeadlineAwarePolicy(concurrency=2)
    daemon = ServingDaemon(router, max_queue=2, policy=policy).start()
    fd = FrontDoor(daemon).start_in_thread()
    cli = FrontDoorClient("127.0.0.1", fd.port)
    try:
        # warm the EMA so the predictor has a basis for hints
        warm = cli.generate(PROMPTS[0], 4)
        assert cli.last_status == 200, warm
        # flood past the admission bound without reading responses
        hits = {"r429": 0, "hinted": 0}
        results = []

        def flood(p):
            c = FrontDoorClient("127.0.0.1", fd.port)
            r = c.generate(p, 4, deadline_s=60.0)
            results.append((c.last_status, c.last_headers, r))

        threads = [threading.Thread(target=flood, args=(PROMPTS[i % 4],))
                   for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=WAIT_S)
        for status, headers, body in results:
            if status == 429:
                hits["r429"] += 1
                assert "queue" in body["error"]
                if body.get("retry_after_s") is not None:
                    hits["hinted"] += 1
                    assert "retry-after" in headers
                    assert int(headers["retry-after"]) >= 1
                    assert body["retry_after_s"] > 0
        assert hits["r429"] >= 1          # the bound actually hit
        assert hits["hinted"] >= 1        # warm predictor produced hints
        assert daemon.counters["rejected_with_hint"] >= 1
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            if daemon.conservation()["outstanding"] == 0:
                break
            time.sleep(0.02)
        assert daemon.conservation()["conserved"]
        # one scrape, both worlds: the door's rejects beside the tier's
        text = cli.metrics()
        assert "frontdoor_requests" in text and "frontdoor_rejected" in text
    finally:
        fd.stop()
        daemon.drain(timeout=30.0)
        daemon.close()


def test_503_after_drain(model_and_params):
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    daemon = ServingDaemon(router, max_queue=8).start()
    fd = FrontDoor(daemon).start_in_thread()
    cli = FrontDoorClient("127.0.0.1", fd.port)
    try:
        assert cli.generate(PROMPTS[0], 2)["status"] == "done"
        daemon.drain(timeout=30.0)
        body = cli.generate(PROMPTS[1], 2)
        assert cli.last_status == 503
        assert "draining" in body["error"] or "closed" in body["error"]
    finally:
        fd.stop()
        daemon.close()


# ----------------------------------------------------------------------
# protocol edges


def test_protocol_edges_and_observability(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    # 400: field named in the error
    bad = cli.generate([], 4)
    assert cli.last_status == 400 and "prompt" in bad["error"]
    bad = cli.generate([1], 4, sampling={"beam": 3})
    assert cli.last_status == 400 and "beam" in bad["error"]
    # 404 / 405
    assert cli._json_call("GET", "/v2/nothing") is not None
    assert cli.last_status == 404
    cli._json_call("GET", "/v1/generate")
    assert cli.last_status == 405
    cli._json_call("POST", "/healthz", {})
    assert cli.last_status == 405
    # healthz: census + conservation
    ok = cli.generate(PROMPTS[0], 4)
    assert ok["status"] == "done"
    h = cli.healthz()
    assert cli.last_status == 200
    assert h["status"] == "ok" and h["healthy"] == 2
    assert set(h["replicas"]) == {"0", "1"}
    assert h["replicas"]["0"]["state"] == "healthy"
    assert h["conservation"]["conserved"] is True
    # metrics: one scrape carries frontend AND tier counters
    text = cli.metrics()
    assert cli.last_status == 200
    assert "frontdoor_requests" in text
    assert "frontdoor_bad_requests" in text


def test_healthz_degrades_when_no_replica(model_and_params):
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    daemon = ServingDaemon(router, max_queue=8,
                           liveness_timeout_s=300.0).start()
    fd = FrontDoor(daemon).start_in_thread()
    cli = FrontDoorClient("127.0.0.1", fd.port)
    try:
        rep = router.replicas[0]
        router._fail_replica(rep, RuntimeError("induced for healthz test"))
        h = cli.healthz()
        assert cli.last_status == 503
        assert h["status"] == "degraded" and h["healthy"] == 0
        assert h["replicas"]["0"]["state"] == "failed"
    finally:
        fd.stop()
        daemon.close()


def test_shared_registry_single_scrape(model_and_params):
    model, params = model_and_params
    registry = MetricsRegistry()
    telemetry = Telemetry(registry=registry)
    router = Router(_factory(model, params), 1, telemetry=telemetry)
    daemon = ServingDaemon(router, max_queue=8).start()
    fd = FrontDoor(daemon).start_in_thread()
    try:
        assert fd.registry is registry   # resolved from daemon telemetry
        cli = FrontDoorClient("127.0.0.1", fd.port)
        assert cli.generate(PROMPTS[0], 2)["status"] == "done"
        text = cli.metrics()
        assert "frontdoor_requests" in text
    finally:
        fd.stop()
        daemon.drain(timeout=30.0)
        daemon.close()


def test_connection_capacity_503(tier):
    daemon, fd, _tracer = tier
    fd.max_connections = 0               # everything is over capacity now
    try:
        cli = FrontDoorClient("127.0.0.1", fd.port)
        body = cli.healthz()
        assert cli.last_status == 503
        assert "capacity" in body["error"]
        assert cli.last_headers["retry-after"] == "1"
    finally:
        fd.max_connections = 64


def test_start_in_thread_idempotent_stop_and_rebind_error(tier):
    daemon, fd, _tracer = tier
    # a second front door on the SAME port must fail to bind, loudly
    clash = FrontDoor(daemon, port=fd.port)
    with pytest.raises(OSError):
        clash.start_in_thread()
    clash.stop()        # no-op: never started


# ----------------------------------------------------------------------
# liveness guards (ISSUE 18 satellites): keep-alive pings + slow-loris


def test_sse_keepalive_pings_on_stalled_stream(model_and_params):
    """A stream with no tokens moving (daemon not yet started — the
    stalled-slot regression) emits ``: ping`` comment frames every
    ``keepalive_s``; once the tier starts, the stream completes with
    full token parity — pings are transparent to the SSE parser."""
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    daemon = ServingDaemon(router, max_queue=8)      # NOT started: stalled
    fd = FrontDoor(daemon, keepalive_s=0.1).start_in_thread()
    try:
        cli = FrontDoorClient("127.0.0.1", fd.port)
        got = {}

        def consume():
            got["tokens"] = list(cli.stream(PROMPTS[0], 4))
            got["terminal"] = cli.last_terminal

        t = threading.Thread(target=consume)
        t.start()
        deadline = time.monotonic() + WAIT_S
        while (time.monotonic() < deadline
               and fd.counters["keepalive_pings"] < 3):
            time.sleep(0.02)
        assert fd.counters["keepalive_pings"] >= 3   # idle stream kept warm
        daemon.start()                               # un-stall the tier
        t.join(timeout=WAIT_S)
        assert not t.is_alive()
        assert got["terminal"]["status"] == "done"
        dr = daemon.submit(PROMPTS[0], 4)
        assert got["tokens"] == list(daemon.stream(dr))
        assert cli.last_event_id == len(got["tokens"]) - 1
    finally:
        fd.stop()
        daemon.close()


def test_slow_loris_gets_408_and_frees_capacity(model_and_params):
    """Clients that dribble (or never send) their request hold a
    connection slot only until ``body_timeout_s``: each gets a 408
    (counted ``read_timeout``), and the freed capacity serves a normal
    request afterwards — the loris flood cannot brown out the door."""
    model, params = model_and_params
    router = Router(_factory(model, params), 1)
    daemon = ServingDaemon(router, max_queue=8).start()
    fd = FrontDoor(daemon, max_connections=3,
                   body_timeout_s=1.5).start_in_thread()
    try:
        loris = []
        for i in range(3):
            s = socket.create_connection(("127.0.0.1", fd.port), timeout=30)
            s.settimeout(30)
            if i == 2:
                # complete head, promised body that never comes
                s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                          b"Content-Type: application/json\r\n"
                          b"Content-Length: 64\r\n\r\n")
            else:
                # head never finishes
                s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n")
            loris.append(s)
        # while the loris hold every slot, the door answers 503, not hangs
        over = FrontDoorClient("127.0.0.1", fd.port, timeout=30)
        body = over.healthz()
        assert over.last_status == 503, body
        assert "capacity" in body["error"]
        # each loris gets its 408 verdict when the read deadline lapses
        for s in loris:
            data = b""
            while b"\r\n\r\n" not in data:
                chunk = s.recv(4096)
                if not chunk:
                    break
                data += chunk
            assert b"408" in data.split(b"\r\n", 1)[0], data[:120]
            s.close()
        assert fd.counters["read_timeout"] == 3
        # the slots are free again: a real request sails through
        cli = FrontDoorClient("127.0.0.1", fd.port)
        out = cli.generate(PROMPTS[0], 3)
        assert cli.last_status == 200 and out["status"] == "done"
    finally:
        fd.stop()
        daemon.drain(timeout=30.0)
        daemon.close()


# ----------------------------------------------------------------------
# idempotency (ISSUE 18): retried POSTs bind to the original execution


def test_idempotent_unary_retry_binds_to_original(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    first = cli.generate(PROMPTS[0], 4, idempotency_key="once")
    assert cli.last_status == 200 and first["status"] == "done"
    submitted = daemon.counters["submitted"]
    retry = cli.generate(PROMPTS[0], 4, idempotency_key="once")
    assert cli.last_status == 200
    # same execution: same id, same tokens, NO second submit
    assert retry["id"] == first["id"]
    assert retry["tokens"] == first["tokens"]
    assert retry["resume_from"] == 0
    assert daemon.counters["submitted"] == submitted
    assert fd.counters["idempotent_hits"] == 1
    # the fingerprint ignores delivery metadata: a retry with a fresher
    # deadline is the SAME request, not a conflict
    again = cli.generate(PROMPTS[0], 4, idempotency_key="once",
                         deadline_s=120.0)
    assert cli.last_status == 200 and again["id"] == first["id"]


def test_idempotency_key_reuse_different_body_422(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    first = cli.generate(PROMPTS[0], 4, idempotency_key="bound")
    assert cli.last_status == 200
    # different prompt under the same key: a client bug, named as such
    clash = cli.generate(PROMPTS[1], 4, idempotency_key="bound")
    assert cli.last_status == 422
    assert "Idempotency-Key" in clash["error"]
    assert clash["id"] == first["id"]
    # different sampling is a different fingerprint too
    cli.generate(PROMPTS[0], 4, idempotency_key="bound",
                 sampling={"temperature": 0.5, "seed": 3})
    assert cli.last_status == 422
    assert fd.counters["idempotent_conflicts"] == 2
    assert daemon.conservation()["conserved"]


def test_keyed_disconnect_survives_and_resumes_exact_suffix(tier):
    """The exactly-once reconnect story on one socket pair: a keyed SSE
    client is severed mid-stream; the request keeps generating (no
    cancel); the retry with ``Last-Event-ID`` receives exactly the
    missing suffix, stitching a duplicate-free, gap-free transcript."""
    daemon, fd, _tracer = tier
    body = json.dumps({"prompt": list(PROMPTS[0]), "max_new": 6,
                       "stream": True}).encode()
    sock = socket.create_connection(("127.0.0.1", fd.port), timeout=30)
    sock.sendall(
        b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
        b"Content-Type: application/json\r\n"
        b"Idempotency-Key: sever\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    sock.recv(64)          # stream is live on the wire
    sock.close()           # client vanishes mid-stream
    deadline = time.monotonic() + WAIT_S
    while (time.monotonic() < deadline
           and fd.counters["disconnects"] < 1):
        time.sleep(0.02)
    assert fd.counters["disconnects"] >= 1
    # keyed request SURVIVES the disconnect: it runs to done, not
    # cancelled — retry-ability is what the key asked for
    while time.monotonic() < deadline:
        cons = daemon.conservation()
        if cons["outstanding"] == 0:
            break
        time.sleep(0.02)
    assert cons["conserved"] and cons["outstanding"] == 0
    assert cons["done"] == cons["submitted"] == 1
    assert cons["cancelled"] == 0
    assert fd.counters["disconnect_cancels"] == 0
    # reconnect claiming tokens [0, 2) were received: the resume serves
    # ids 2.. exactly, and prefix + suffix == the uncrashed stream
    cli = FrontDoorClient("127.0.0.1", fd.port)
    suffix = list(cli.stream(PROMPTS[0], 6, idempotency_key="sever",
                             last_event_id=1))
    assert cli.last_terminal["status"] == "done"
    assert cli.last_terminal["n_tokens"] == 6
    assert fd.counters["resumes"] == 1
    dr = daemon.submit(PROMPTS[0], 6)
    want = list(daemon.stream(dr))
    assert suffix == want[2:]
    assert cli.last_event_id == 5      # ids continue the logical index
    # a second full resume from the very start replays everything
    cli2 = FrontDoorClient("127.0.0.1", fd.port)
    assert list(cli2.stream(PROMPTS[0], 6,
                            idempotency_key="sever")) == want


def test_last_event_id_must_be_integer_400(tier):
    _daemon, fd, _tracer = tier
    body = json.dumps({"prompt": [1, 2], "max_new": 2,
                       "stream": True}).encode()
    sock = socket.create_connection(("127.0.0.1", fd.port), timeout=30)
    sock.sendall(
        b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
        b"Content-Type: application/json\r\n"
        b"Last-Event-ID: not-a-number\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    data = b""
    sock.settimeout(30)
    while b"\r\n\r\n" not in data:
        chunk = sock.recv(4096)
        if not chunk:
            break
        data += chunk
    sock.close()
    assert b"400" in data.split(b"\r\n", 1)[0]


# ----------------------------------------------------------------------
# distributed tracing at the edge (ISSUE 19)


def test_trace_headers_echoed_unary_and_sse(tier):
    daemon, fd, tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    # unary: server-generated id + traceparent
    out = cli.generate([1, 2, 3], 2)
    assert cli.last_status == 200
    assert cli.last_headers["x-request-id"] == str(out["id"])
    ctx = TraceContext.parse_traceparent(cli.last_headers["traceparent"])
    assert ctx is not None and ctx.sampled
    # SSE: same contract on the stream head
    toks = list(cli.stream([1, 2, 3], 2))
    assert len(toks) == 2
    assert "x-request-id" in cli.last_headers
    assert TraceContext.parse_traceparent(
        cli.last_headers["traceparent"]) is not None
    daemon.drain(timeout=WAIT_S)
    assert tracer.open_spans == 0


def test_client_request_id_honored_and_sanitized(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    # a clean client id is echoed verbatim, on unary AND SSE
    cli.generate([1, 2], 2, extra_headers={"X-Request-Id": "cli.id:ok-1"})
    assert cli.last_headers["x-request-id"] == "cli.id:ok-1"
    list(cli.stream([1, 2], 2, extra_headers={"X-Request-Id": "cli.id:ok-2"}))
    assert cli.last_headers["x-request-id"] == "cli.id:ok-2"
    # malformed (spaces/injection) and oversized ids fall back to the
    # daemon id — a hostile header never reaches the response verbatim
    for bad in ("not ok", "x" * 200, "new\tline"):
        out = cli.generate([1, 2], 2, extra_headers={"X-Request-Id": bad})
        assert cli.last_headers["x-request-id"] == str(out["id"])
    daemon.drain(timeout=WAIT_S)


def test_client_traceparent_joins_the_trace(tier):
    daemon, fd, tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    want_tid = "ab" * 16
    sent = f"00-{want_tid}-{'cd' * 8}-01"
    cli.generate([1, 2, 3], 2, extra_headers={"traceparent": sent})
    got = TraceContext.parse_traceparent(cli.last_headers["traceparent"])
    assert got.trace_id == want_tid          # joined, not re-minted
    assert got.span_id != "cd" * 8           # but with our own span id
    # a malformed traceparent is ignored: fresh trace, request still 200
    cli.generate([1, 2, 3], 2, extra_headers={"traceparent": "junk-header"})
    assert cli.last_status == 200
    fresh = TraceContext.parse_traceparent(cli.last_headers["traceparent"])
    assert fresh is not None and fresh.trace_id != want_tid
    daemon.drain(timeout=WAIT_S)
    assert tracer.open_spans == 0


def test_request_trace_debug_endpoint(tier):
    daemon, fd, _tracer = tier
    cli = FrontDoorClient("127.0.0.1", fd.port)
    cli.generate([1, 2, 3], 2, extra_headers={"X-Request-Id": "dbg-1"})
    echoed = TraceContext.parse_traceparent(
        cli.last_headers["traceparent"]).trace_id
    daemon.drain(timeout=WAIT_S)
    doc = cli.request_trace("dbg-1")
    assert cli.last_status == 200
    assert doc["request_id"] == "dbg-1"
    names = {e["name"] for e in doc["events"]}
    assert {"http_request", "daemon_request", "request"} <= names
    # the id the header echoed is the id the lookup resolves
    assert doc["trace_id"] == echoed
    # unknown id -> 404, wrong method -> 405
    cli.request_trace("never-seen")
    assert cli.last_status == 404
    cli._json_call("POST", "/v1/requests/dbg-1/trace", {})
    assert cli.last_status == 405


def test_metrics_openmetrics_negotiation(model_and_params):
    model, params = model_and_params
    telemetry = Telemetry(interval_s=0.05)
    tracer = Tracer()
    router = Router(_factory(model, params, tracer=tracer,
                             telemetry=telemetry), 1, tracer=tracer,
                    telemetry=telemetry)
    daemon = ServingDaemon(router, max_queue=8).start()
    fd = FrontDoor(daemon).start_in_thread()
    try:
        cli = FrontDoorClient("127.0.0.1", fd.port)
        cli.generate([1, 2, 3], 3)
        daemon.drain(timeout=WAIT_S)
        om = cli.metrics(accept="application/openmetrics-text")
        assert om.rstrip().endswith("# EOF")
        ex = [l for l in om.splitlines() if " # {" in l]
        assert ex and any('trace_id="' in l for l in ex)
        # the default scrape stays classic Prometheus
        pm = cli.metrics()
        assert "# EOF" not in pm and " # {" not in pm
    finally:
        fd.stop()
        if not daemon._closed:
            daemon.close()


def test_shed_request_gets_shed_span_and_tail_keeps(model_and_params):
    """A 503-shed request must leave a terminal ``shed`` span that the
    tail sampler keeps even at ``trace_sample_rate=0`` (satellite 6)."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        TraceSampler,
        trace_forest,
    )

    model, params = model_and_params
    tracer = Tracer()
    router = Router(_factory(model, params, tracer=tracer), 1,
                    tracer=tracer)
    daemon = ServingDaemon(router, max_queue=8).start()
    fd = FrontDoor(daemon, trace_sample_rate=0.0).start_in_thread()
    try:
        cli = FrontDoorClient("127.0.0.1", fd.port)
        ok = cli.generate([1, 2], 2)          # served -> head-dropped
        assert cli.last_status == 200
        daemon.drain(timeout=WAIT_S)          # draining -> next is shed
        shed = cli.generate([1, 2], 2)
        assert cli.last_status == 503, shed
        shed_tp = cli.last_headers.get("traceparent")
        assert shed_tp is not None            # sheds are findable too
        shed_tid = TraceContext.parse_traceparent(shed_tp).trace_id
    finally:
        fd.stop()
        if not daemon._closed:
            daemon.close()
    assert tracer.open_spans == 0
    forest = trace_forest(tracer.to_doc(sampler=fd.sampler))
    assert shed_tid in forest                 # tail-kept
    g = forest[shed_tid]
    assert "shed" in g["names"] and "shed" in g["statuses"]
    # the successfully served trace was head-dropped at rate 0:
    # only the shed trace's front-door span survives export
    assert all(tid == shed_tid for tid, f in forest.items()
               if "http_request" in f["names"])


# ----------------------------------------------------------------------
# failover behind connected clients: the wire inherits the tier's
# guarantee, and each finished stream is one connected span tree


def test_pump_kill_behind_connected_sse_clients(model_and_params, tmp_path,
                                                pools_refcount_zero):
    """``daemon-pump`` chaos kills one of two pumps while SSE clients are
    connected: every stream still ends ``done`` with its tokens delivered
    exactly once, conservation stays exact, ``/healthz`` shows the casualty,
    and every stream's trace — found by the ``traceparent`` the door
    echoed — is ONE connected tree from the HTTP accept through admission,
    prefill and decode, with a span link from a replayed dispatch back to
    the attempt that died."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
        FaultInjector,
        FaultPlan,
        FaultSpec,
    )
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        trace_forest,
        validate_trace,
    )

    model, params = model_and_params
    inj = FaultInjector(FaultPlan(seed=5, faults=(
        FaultSpec(site="daemon-pump", kind="raise", at=(0,)),)))
    tracer = Tracer()
    router = Router(_factory(model, params, tracer=tracer, chaos=inj), 2,
                    chaos=inj, tracer=tracer)
    router.prewarm()
    daemon = ServingDaemon(router, max_queue=64,
                           liveness_timeout_s=30.0).start()
    fd = FrontDoor(daemon).start_in_thread()
    prompts = [PROMPTS[i % len(PROMPTS)] + [1 + i] for i in range(6)]
    results = {}

    def client(i, prompt):
        cli = FrontDoorClient("127.0.0.1", fd.port, timeout=WAIT_S)
        toks = list(cli.stream(prompt, 4, deadline_s=WAIT_S))
        results[i] = (toks, cli.last_terminal,
                      cli.last_headers.get("traceparent"))

    try:
        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=WAIT_S)
        assert daemon.router.failovers >= 1
        assert daemon.counters["pump_faults"] >= 1
        # the same prompts, greedy, through the tier that survived
        refs = [daemon.submit(p, 4) for p in prompts]
        assert all(dr.wait(timeout=WAIT_S) for dr in refs)
        for i, dr in enumerate(refs):
            toks, terminal, _ = results[i]        # no stream was dropped
            assert terminal["status"] == "done"
            assert toks == list(dr.tokens)        # ... or replayed twice
            assert terminal["n_tokens"] == len(toks)
        health = FrontDoorClient("127.0.0.1", fd.port).healthz()
        assert health["healthy"] == 1 and sorted(
            v["state"] for v in health["replicas"].values()) == [
                "failed", "healthy"]
        assert daemon.conservation()["conserved"]
    finally:
        fd.stop()
        drained = daemon.drain(timeout=30.0)
        pools = pools_refcount_zero(daemon.router)
        daemon.close()
    assert drained and pools
    assert tracer.open_spans == 0
    path = str(tmp_path / "failover.json")
    tracer.export_trace(path)
    assert validate_trace(path) == []
    with open(path) as fh:
        doc = json.load(fh)
    forest = trace_forest(doc)
    for _, _, tp in results.values():
        g = forest[TraceContext.parse_traceparent(tp).trace_id]
        assert g["connected"], g
        assert {"http_request", "daemon_request", "request", "prefill",
                "decode"} <= set(g["names"]), g["names"]
    assert any(e.get("args", {}).get("links") for e in doc["traceEvents"])
