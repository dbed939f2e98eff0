"""The program's spans on the profiler's clock (ISSUE 25, ``host_span``).

The decisive properties:

* PRESENCE AND NESTING — under a ``jax.profiler`` session every phase of
  ``InferenceEngine.step()`` and every ``CompileTracker.site`` lands in the
  trace's host plane under its documented name, each ``engine.*`` phase
  inside an ``engine.step``, one ``engine.step`` per ``step()`` call.
* NO BEHAVIOUR — tokens, ``ServingStats`` counts and the ``Tracer`` ring's
  event names are the same with a session open and with none, in every
  window mode; with none a span leaves nothing behind.
* THE SITE CONTRACT — ``CompileTracker.site`` still attributes compiles to
  the innermost label.
* NAMED PROGRAMS — the four formerly anonymous cache programs lower to
  modules named after their functions, which is how a device trace names
  them.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
    CompileTracker,
    Tracer,
    host_span,
)

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)
PHASES = ("engine.admit", "engine.land", "engine.first_pick",
          "engine.dispatch", "engine.overlap", "engine.readback",
          "engine.emit", "engine.reset")


@pytest.fixture(scope="module")
def model_and_params():
    model = get_model("causal_lm", **KW)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model_and_params, **kw):
    model, params = model_and_params
    tracer = Tracer()
    return InferenceEngine(
        model, params, slots=2, max_len=32,
        scheduler=FIFOScheduler(max_len=32, buckets=(8, 16), tracer=tracer),
        kv_page_size=4, kv_pages=32, tracer=tracer, **kw)


def _serve(engine):
    """Three requests through two slots; returns (tokens, step() calls)."""
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(1, 16, n).astype(np.int32), max_new=5)
            for n in (5, 9, 12)]
    steps = 0
    while engine.has_work:
        engine.step()
        steps += 1
        assert steps < 200
    assert all(r.status == "done" for r in reqs)
    return [list(r.generated) for r in reqs], steps


class _Session:
    """A profiler session over a block, read back as the host plane's
    ``(name, start_ns, end_ns, stats)`` spans of the program's own names."""

    def __init__(self, log_dir):
        self.dir = str(log_dir)
        self.spans = []

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("engine.", "site:", "test.")):
                        self.spans.append(
                            (e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns), dict(e.stats)))
        return False

    def named(self, name):
        return [s for s in self.spans if s[0] == name]

    def starting(self, prefix):
        return [s for s in self.spans if s[0].startswith(prefix)]


def _inside(span, outers):
    return any(o[1] <= span[1] and span[2] <= o[2] for o in outers)


def test_step_phases_land_in_the_profilers_host_plane(model_and_params, tmp_path):
    engine = _engine(model_and_params)
    engine.prewarm()
    with _Session(tmp_path) as trace:
        _tokens, steps = _serve(engine)
    engine.close()
    step_spans = trace.named("engine.step")
    assert len(step_spans) == steps
    assert all("occupied" in s[3] for s in step_spans)
    for name in PHASES:
        found = trace.named(name)
        assert found, f"no {name} span in the trace"
        assert all(_inside(s, step_spans) for s in found), name
    # once per step() call / per window, never more
    assert len(trace.named("engine.admit")) == steps
    assert len(trace.named("engine.reset")) == steps
    windows = len(trace.named("engine.dispatch"))
    assert 0 < windows <= steps
    for name in ("engine.overlap", "engine.readback", "engine.emit"):
        assert len(trace.named(name)) == windows
    # ids and counts ride as the event's stats, never in its name
    land = trace.named("engine.land")
    assert sorted(s[3]["req"] for s in land) == [0, 1, 2]
    assert all(s[3]["pages"] > 0 and s[3]["radix_blocks"] == 0 for s in land)
    picks = trace.named("engine.first_pick")
    assert len(picks) == 3 and all(_inside(s, land) for s in picks)
    # the pick's span is wider than its site: it holds the host read too
    for site in trace.named("site:first_pick"):
        assert _inside(site, picks)
    # every dispatch site is a span; a prefill is dispatched from admission
    # or from the overlap seam behind a window
    for prefix in ("site:slot_insert", "site:decode_window[k1]",
                   "site:slot_reset"):
        assert trace.starting(prefix), prefix
    prefills = trace.starting("site:prefill[b")
    assert len(prefills) == 3
    hosts = trace.named("engine.admit") + trace.named("engine.overlap")
    assert all(_inside(s, hosts) for s in prefills)
    assert all(_inside(s, trace.named("engine.dispatch"))
               for s in trace.starting("site:decode_window"))


MODES = {
    "plain": {},
    "decode_ahead": {"decode_ahead": 4},
    "speculative": {"speculative": "ngram", "draft_len": 3},
    "chunked_prefill": {"prefill_chunk": 4},
}
COUNTS = ("n_requests", "n_done", "tokens_generated", "decode_steps",
          "n_windows", "window_steps", "window_waste_steps", "radix_hits",
          "radix_misses", "n_prefill_chunks", "drafted_tokens",
          "accepted_tokens")


@pytest.mark.parametrize("mode", sorted(MODES))
def test_a_profiler_session_changes_nothing_the_engine_does(
        model_and_params, tmp_path, mode):
    def run():
        engine = _engine(model_and_params, **MODES[mode])
        tokens, steps = _serve(engine)
        summary = engine.stats.summary()
        names = [e["name"] for e in engine._tracer.events()]
        engine.close()
        return tokens, steps, {k: summary[k] for k in COUNTS}, names

    assert not jax.profiler.TraceAnnotation.is_enabled()
    off = run()  # no session: the spans are entered and record nothing
    with _Session(tmp_path) as trace:
        on = run()
    assert on == off
    assert off[2]["tokens_generated"] == 15
    # only the traced run's steps are in the trace: the untraced run's
    # spans left nothing behind
    assert len(trace.named("engine.step")) == on[1]
    assert trace.named("engine.emit") and trace.starting("site:")


def test_compile_site_still_attributes_and_nests_under_its_span(tmp_path):
    tracker = CompileTracker.install()
    a, b, c = (jnp.asarray(np.arange(n, dtype=np.float32)) for n in (5, 7, 9))
    before = tracker.snapshot()
    with _Session(tmp_path) as trace:
        with host_span("test.outer", n=1):
            with tracker.site("hs_outer"):
                jax.jit(lambda x: x * 3 + 1)(a).block_until_ready()
                with tracker.site("hs_inner[b8]"):
                    jax.jit(lambda x: x * 5 - 2)(b).block_until_ready()
                jax.jit(lambda x: x * 7 + 3)(c).block_until_ready()
    by_site = CompileTracker.delta(tracker.snapshot(), before)["by_site"]
    assert by_site["hs_outer"]["n"] == 2 and by_site["hs_inner[b8]"]["n"] == 1
    (outer,), (inner,) = trace.named("site:hs_outer"), trace.named("site:hs_inner[b8]")
    (test_outer,) = trace.named("test.outer")
    assert _inside(inner, [outer]) and _inside(outer, [test_outer])
    assert test_outer[3] == {"n": 1}
    # a site left by an exception still pops its label and closes its span
    with pytest.raises(RuntimeError):
        with tracker.site("hs_raises"):
            raise RuntimeError("boom")
    assert not getattr(tracker._tl, "stack", None)


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _lowered(engine, program):
    if program == "_reset_rows":
        return engine._reset.lower(
            engine.cache, engine._dev(np.zeros((engine.slots,), bool)))
    if program == "_insert_row":
        row_cache, _ = engine._prefill_row(
            engine.params, jnp.zeros((1, 8), jnp.int32), jnp.asarray([3], jnp.int32))
        bt = engine._dev(np.zeros((engine.max_len // 4,), np.int32))
        return engine._insert.lower(engine.cache, row_cache, bt, _i32(0))
    if program == "_page_write":
        payload = engine._page_gather(engine.cache, _i32(1))
        return engine._page_write.lower(engine.cache, payload, _i32(1))
    bt = engine._dev(np.zeros((engine.max_len // 4,), np.int32))
    return engine._bt_install.lower(engine.cache, bt, _i32(0), _i32(3))


@pytest.fixture(scope="module")
def idle_engine(model_and_params):
    engine = _engine(model_and_params)
    yield engine
    engine.close()


@pytest.mark.parametrize("program", ["_insert_row", "_reset_rows",
                                     "_page_write", "_bt_install"])
def test_cache_programs_lower_under_their_own_names(idle_engine, program):
    text = _lowered(idle_engine, program).as_text()
    assert f"module @jit_{program}" in text.split("\n", 1)[0]
    assert "lambda" not in text.split("\n", 1)[0]
