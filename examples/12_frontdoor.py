"""The internet-shaped front door: HTTP in, SSE out, elastic capacity (ISSUE 17).

Everything below examples/10 and /11 talked to the tier through Python
calls.  This example puts the :class:`~distributed_tensorflow_ibm_mnist_tpu.
serving.FrontDoor` in front of the daemonized tier — a stdlib-asyncio
HTTP server any ``curl`` can reach — and walks its whole surface:

* **unary** — ``POST /v1/generate`` with a JSON body, tokens back in one
  JSON response;
* **streaming** — the same endpoint with ``"stream": true`` answers
  ``text/event-stream``: one SSE event per token as the daemon's
  delivery thread hands it over (``loop.call_soon_threadsafe`` is the
  only bridge — no polling), a terminal ``event: end`` with the request
  id and status;
* **operations** — ``GET /healthz`` (replica census + the conservation
  invariant) and ``GET /metrics`` (Prometheus text; the front door's
  counters share the daemon's registry so one scrape sees the whole
  tier);
* **elasticity** — an :class:`~distributed_tensorflow_ibm_mnist_tpu.
  serving.Autoscaler` watching the same telemetry scales the tier up
  under backlog (warm respawn through the persistent compile cache) and
  retires — drain first, drop nothing — when traffic recedes.

The tiny untrained LM makes the TOKENS meaningless; what the example
demonstrates is protocol and lifecycle mechanics, which are exactly the
parts that transfer to a real checkpoint.

    JAX_PLATFORMS=cpu python examples/12_frontdoor.py
"""

import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo-root import without install

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    Autoscaler,
    FIFOScheduler,
    FrontDoor,
    FrontDoorClient,
    InferenceEngine,
    Router,
    ServingDaemon,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import enable_compile_cache

VOCAB = 16
MAX_LEN = 16


def main():
    model = get_model("causal_lm", num_classes=VOCAB, dim=32, depth=1,
                      heads=2, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # the persistent compile cache is what makes the autoscaler's
    # respawns warm: replica 0's prewarm populates it, every later
    # spawn reads it back instead of recompiling
    enable_compile_cache(cpu=True)

    def make_engine(tid):
        return InferenceEngine(
            model, params, slots=2, max_len=MAX_LEN, kv_page_size=4,
            scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(8,),
                                    max_queue=64),
            trace_tid=tid)

    router = Router(make_engine, 1)
    router.prewarm()
    daemon = ServingDaemon(router, max_queue=64,
                           liveness_timeout_s=30.0).start()
    fd = FrontDoor(daemon).start_in_thread()
    print(f"front door listening on http://127.0.0.1:{fd.port}")
    print("the curl equivalents of everything below:")
    print(f"  curl -s http://127.0.0.1:{fd.port}/healthz")
    print(f"  curl -s http://127.0.0.1:{fd.port}/metrics")
    print(f"  curl -s -X POST http://127.0.0.1:{fd.port}/v1/generate "
          "-d '{\"prompt\": [1, 2, 3], \"max_new\": 4}'")
    print(f"  curl -sN -X POST http://127.0.0.1:{fd.port}/v1/generate "
          "-d '{\"prompt\": [1, 2, 3], \"max_new\": 4, \"stream\": true}'")

    cli = FrontDoorClient("127.0.0.1", fd.port)
    try:
        # -- unary ------------------------------------------------------
        body = cli.generate([1, 2, 3], 4)
        print(f"\nunary:     HTTP {cli.last_status} -> "
              f"tokens {body['tokens']} (request {body['id']})")

        # -- streaming: tokens arrive one SSE event at a time -----------
        got = []
        for tok in cli.stream([1, 2, 3], 4,
                              sampling={"temperature": 0.8, "seed": 7}):
            got.append(tok)
        term = cli.last_terminal
        print(f"streaming: {len(got)} SSE events {got}, "
              f"terminal status {term['status']!r}")

        # -- operations -------------------------------------------------
        hz = cli.healthz()
        print(f"healthz:   {hz['status']} — "
              f"{hz['healthy']}/{hz['n_replicas']} replicas healthy, "
              f"conservation "
              f"{'holds' if hz['conservation']['conserved'] else 'BROKEN'}")
        scrape = [ln for ln in cli.metrics().splitlines()
                  if "frontdoor_requests" in ln and not ln.startswith("#")]
        print(f"metrics:   {scrape[0]} (one scrape covers daemon + door)")

        # -- elasticity: backlog scales up, idleness retires ------------
        asc = Autoscaler(daemon, min_replicas=1, max_replicas=2,
                         up_backlog_per_slot=1.0, down_occupancy=0.5,
                         hysteresis_up=1, hysteresis_down=2)
        rng = np.random.default_rng(3)
        burst = [threading.Thread(
            target=cli_burst, args=(fd.port, rng.integers(1, VOCAB, 4)))
            for _ in range(10)]
        for th in burst:
            th.start()
        while not any(e["action"] == "up" for e in asc.events):
            asc.tick()
        up = asc.events[-1]
        print(f"\nburst of {len(burst)} streams -> scale-UP: replica "
              f"{up['replica']} spawned in {up['spawn_s']:.2f}s "
              f"({'warm restart' if up['warm'] else 'fresh spawn, compile-cache-warmed'}), "
              f"backlog/slot was "
              f"{up['signals']['backlog_per_slot']:.2f}")
        for th in burst:
            th.join()
        while not any(e["action"] == "down" for e in asc.events):
            asc.tick()
        print(f"traffic gone -> scale-DOWN: replica "
              f"{asc.events[-1]['replica']} drained and retired "
              f"(zero drops is the retire contract)")
        print(f"autoscaler: {asc.summary()}")
    finally:
        fd.stop()
        daemon.drain(timeout=30.0)
        daemon.close()
    print("\nfront door closed, tier drained clean")


def cli_burst(port, prompt):
    c = FrontDoorClient("127.0.0.1", port)
    list(c.stream(prompt, 5))


if __name__ == "__main__":
    main()
