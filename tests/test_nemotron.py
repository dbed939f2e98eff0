"""Nemotron-3-Super's three layer kinds (Mamba-2, LatentMoE, attention)
through the serving engine against the plain float32 reference
(``benchmark/reference_nemotron.py``: the benchmark's copy IS the test
suite's), at a tiny preset on the CPU with the kernels interpreted: hidden
64, the published pattern's first period ``MEMEMEM*EME``, 8 Mamba heads of
16 in 2 groups with a state of 32 and a convolution of 4 taps, a latent of
32, 16 relu^2 experts of which rank 1 of 4 holds experts 4-7, top-4, a
shared expert, attention heads of 128 as published.

Tolerances: the tiny model is float32 end to end, like the reference, so
engine and reference differ by reduction order only: 1e-3 on a logit or a
log-probability of a model whose top-2 gaps are ~0.5 is ~1000 times the
float32 noise read (1e-6); states and convolution tails agree to 1e-5
relative; chosen experts are EQUAL ids.  The kernels against the token-by-
token recurrence: 1e-4 relative (float32 products at HIGHEST, the chunked
form sums in another order).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark import nemotron_serve_runner as runner  # noqa: E402
from benchmark import reference_nemotron as ref  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.models import get_model  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.models.nemotron_h import (  # noqa: E402
    LatentMoEBlock,
    NemotronHLM,
)
from distributed_tensorflow_ibm_mnist_tpu.ops import ssd  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.parallel import expert_parallel as ep  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.serving.engine import InferenceEngine  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.serving.stats import ServingStats  # noqa: E402

TOL = 1e-3
DATA = os.path.join(os.path.dirname(ref.__file__), "tests", "data_nemotron")
CHUNK = 16


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(
        harness.load_json(os.path.join(DATA, "BENCHMARK.json")), "tiny-nemotron.agent",
        DATA, seed=13, seconds=0.0, trace=False, rehearse=True)


@pytest.fixture(scope="module")
def model_and_params(cell):
    from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret

    set_interpret(True)
    model = runner.build_model(cell.config, rehearse=True)
    return model, runner.nemotron_weights(model, 13, jnp.float32, cell.config)


def make_engine(model, params, slots=2, **kw):
    kw.setdefault("prefill_chunk", CHUNK)
    return InferenceEngine(model, params, slots=slots, max_len=128,
                           kv_page_size=8, kv_pages=48, decode_ahead=1, **kw)


# a prompt inside one chunk, one that ends on a chunk's edge, one over two
# chunk boundaries (16 and 32 fall inside it) and a short one last, on two
# slots: every slot is reused, the last time by a prompt shorter than the
# convolution's reach into its last tenant
PROMPTS = (5, CHUNK, 40, 2)
NEW = 17     # 16 decode steps after the last chunk's token


@pytest.fixture(scope="module")
def served(model_and_params, cell):
    model, params = model_and_params
    engine = make_engine(model, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cell.config["vocab_size"], n).astype(np.int32)
               for n in PROMPTS]
    reqs = [engine.submit(p, max_new=NEW) for p in prompts]
    held = {}
    while engine.has_work:
        engine.step()
        for i, r in enumerate(reqs):
            # a request's leaves as they stand while it still holds its slot
            if r in engine._slot_req and len(r.generated) == NEW - 1:
                slot = engine._slot_req.index(r)
                held[i] = {k: [np.asarray(engine.cache[b][k][slot])
                               for b in runner._blocks(cell.config, "M")]
                           for k in ("ssm_state", "conv_state")}
    assert all(r.status == "done" for r in reqs)
    engine.sync_expert_load()
    return engine, prompts, reqs, held


def _reference(cell, params, tokens, rows, audit=None, low=()):
    return np.asarray(ref.logits_rows(params, tokens, rows, ref.shape_of(cell.config),
                                      audit, low=low))


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - b) / np.linalg.norm(b))


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_prefill_and_cached_decode_match_reference(cell, served, i):
    """Chunked prefill (chunk edges inside the prompt), then 16 decode steps
    through the cache: every logit the engine picked from is the reference's,
    and after ``fed`` tokens each Mamba layer's state and convolution tail
    in the row's slot are the reference's."""
    engine, prompts, reqs, held = served
    p, g = prompts[i], np.asarray(reqs[i].generated, np.int32)
    fed = p.size + NEW - 2      # leaves captured with NEW - 1 tokens generated
    audit = {"state_at": fed}
    at = _reference(cell, engine.params, np.concatenate([p, g]),
                    np.arange(p.size - 1, p.size - 1 + g.size), audit)
    picked = at[np.arange(g.size), g]
    assert float(np.max(at.max(-1) - picked)) <= TOL
    logp = picked - np.asarray(jax.nn.logsumexp(at, axis=-1))
    assert float(np.max(np.abs(logp - np.asarray(reqs[i].logprobs)))) <= TOL
    for k in ("ssm_state", "conv_state"):
        assert len(held[i][k]) == len(audit[k]) == 5
        for got, want in zip(held[i][k], audit[k]):
            assert _rel(got, want) <= 1e-5, k


def test_the_check_reads_equal_states_experts_and_counts(cell, model_and_params):
    """The benchmark's check at its tiny preset: in float32 it passes with
    every slot taken, the engine's recorded expert choices at every fed
    token (chunks and decode steps) equal to the reference's; a
    slot-indexing fault (the other row's states) and a device count off by
    one pair do not pass.  Its control is
    ``benchmark/tests/test_rehearse_nemotron.py``'s."""
    engine, _ = runner.build_engine(cell, harness.Setup(0.0))
    seen = runner.observe(engine, cell)
    got = runner.compare(seen, engine.params, cell.config)
    assert got["ok"], got
    assert got["expert_overlap"] == 1.0 and got["load_err"] == 0.0
    assert got["state_err"] <= 1e-5 and got["conv_err"] <= 1e-5
    assert got["state_head_err"] <= 1e-5
    assert got["counted"] == got["expected"] and got["routes_complete"]
    assert got["sampled"] == got["requests"] == cell.config["engine"]["slots"]
    # the judged requests decoded beside every filler
    assert got["live_rows_min"] >= got["requests"] - len(cell.config["check"]["prompts"])
    i, j = seen["sample"][-2:]
    held = {**seen["held"], i: seen["held"][j], j: seen["held"][i]}
    swapped = runner.compare({**seen, "held": held}, engine.params, cell.config)
    assert not swapped["ok"] and swapped["state_err"] > 0.1
    load = np.array(seen["load"])
    load[0, 0] += 1
    off = runner.compare({**seen, "load": load}, engine.params, cell.config)
    assert not off["ok"] and off["load_err"] > 0


def test_counters_follow_scans_updates_and_experts(cell, served):
    engine, prompts, reqs, _ = served
    s = engine.stats.summary()
    fed = sum(p.size + NEW - 1 for p in prompts)
    assert s["expert_assignments"] == fed * 4 * 5   # top-4, five expert layers
    assert s["ssm_scan_tokens"] == sum(p.size for p in prompts) * 5
    assert s["ssm_step_rows"] == len(prompts) * (NEW - 1) * 5
    assert s["expert_assignments_held"] == sum(map(sum, s["expert_load"])) > 0
    assert len(s["expert_load"]) == 5 and len(s["expert_load"][0]) == 4
    assert s["state_rows_total"] == 2
    assert s["paged_kernel_windows"] == s["n_windows"] > 0
    merged = ServingStats.merge([engine.stats, engine.stats])
    for k in ("expert_assignments", "ssm_scan_tokens", "ssm_step_rows"):
        assert merged[k] == 2 * s[k]


def test_registered_and_refused_by_name(model_and_params):
    model, params = model_and_params
    assert isinstance(get_model("nemotron_h", dim=64), NemotronHLM)
    with pytest.raises(ValueError, match="recurrent-state layers.*chunked prefill only"):
        InferenceEngine(model, params, slots=2, max_len=128, kv_page_size=8)
    with pytest.raises(ValueError, match="radix prefix sharing is refused"):
        make_engine(model, params, radix_cache=True)
    with pytest.raises(ValueError, match="without speculative decoding"):
        make_engine(model, params, speculative="ngram")
    with pytest.raises(ValueError, match="expert banks have no int8 form"):
        make_engine(model, params, quant="int8")
    for kw in ({"tp": 2}, {"role": "prefill"}):
        with pytest.raises(ValueError, match="one chip"):
            make_engine(model, params, **kw)
    assert make_engine(model, params)._radix is None


# ----------------------------------------------------------------------
# the kernels against the token-by-token recurrence

H, P, N, G, T = 4, 8, 16, 2, 24


def _recurrence(x, dt, a_log, b, c, s0, n):
    """S_t = exp(dt_t A) S + dt_t x_t B_t^T, y_t = S_t C_t, for t < n."""
    s, ys = np.array(s0, np.float64), []
    a = -np.exp(np.asarray(a_log, np.float64))
    for t in range(n):
        for h in range(H):
            g = h // (H // G)
            s[h] = (np.exp(dt[h, t] * a[h]) * s[h]
                    + np.outer(dt[h, t] * x[h, t], b[g, t]))
        ys.append(np.stack([s[h] @ c[h // (H // G), t] for h in range(H)]))
    return np.stack(ys, 1), s


@pytest.fixture(scope="module")
def seq():
    rng = np.random.default_rng(7)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    return {"x": f(H, T, P), "dt": np.log1p(np.exp(f(H, T) - 1)).astype(np.float32),
            "a_log": np.log(rng.uniform(1, 16, H)).astype(np.float32),
            "b": f(G, T, N), "c": f(G, T, N), "s0": f(H, P, N)}


def _scan(seq, x, dt, b, c, s0, n):
    return ssd.ssd_chunk_scan(jnp.asarray(x), jnp.asarray(dt), seq["a_log"],
                              jnp.asarray(b), jnp.asarray(c), jnp.asarray(s0), n,
                              interpret=True)


@pytest.mark.parametrize("n", [T, 13, 1])
def test_chunk_scan_is_the_recurrence_and_a_padded_tail_does_nothing(seq, n):
    y, s = _scan(seq, seq["x"], seq["dt"], seq["b"], seq["c"], seq["s0"], n)
    want_y, want_s = _recurrence(seq["x"], seq["dt"], seq["a_log"], seq["b"],
                                 seq["c"], seq["s0"], n)
    assert _rel(np.asarray(y)[:, :n], want_y) <= 1e-4
    assert _rel(s, want_s) <= 1e-4


def test_a_chunk_split_at_every_offset_gives_the_same_state(seq):
    """The row's state after T tokens, from one call or from two calls split
    at every offset (the second padded to a whole chunk), and likewise the
    convolution's output and tail carried across the split."""
    whole = np.asarray(_scan(seq, seq["x"], seq["dt"], seq["b"], seq["c"],
                             seq["s0"], T)[1])
    u = jnp.asarray(seq["x"].reshape(H * P, T).T[None])          # (1, T, C)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(4, H * P)), jnp.float32)
    bias = jnp.ones((H * P,), jnp.float32)
    zero = jnp.zeros((1, 3, H * P), jnp.float32)
    conv_whole, tail_whole = ssd.causal_conv(u, zero, w, bias, jnp.asarray([T]))

    def padded(a, k, axis):
        part = np.take(a, np.arange(k, T), axis=axis)
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, k)
        return np.pad(part, pad)

    for k in range(1, T):
        _, s1 = _scan(seq, seq["x"], seq["dt"], seq["b"], seq["c"], seq["s0"], k)
        _, s2 = _scan(seq, padded(seq["x"], k, 1), padded(seq["dt"], k, 1),
                      padded(seq["b"], k, 1), padded(seq["c"], k, 1), s1, T - k)
        assert _rel(s2, whole) <= 1e-4, k
        o1, t1 = ssd.causal_conv(u, zero, w, bias, jnp.asarray([k]))
        o2, t2 = ssd.causal_conv(jnp.asarray(padded(np.asarray(u), k, 1)), t1, w,
                                 bias, jnp.asarray([T - k]))
        np.testing.assert_allclose(np.concatenate([o1[0, :k], o2[0, :T - k]]),
                                   conv_whole[0], rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(t2, tail_whole)


def test_step_kernel_updates_in_place_and_idle_rows_keep_their_state(seq):
    rng = np.random.default_rng(2)
    rows = 3
    x = rng.normal(size=(rows, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (rows, H)).astype(np.float32)
    b, c = (rng.normal(size=(rows, G, N)).astype(np.float32) for _ in range(2))
    state = rng.normal(size=(rows, H, P, N)).astype(np.float32)
    live = np.array([True, False, True])
    y, s = ssd.ssd_step(jnp.asarray(x), jnp.asarray(dt), seq["a_log"], jnp.asarray(b),
                        jnp.asarray(c), jnp.asarray(state), jnp.asarray(live),
                        interpret=True)
    for r in range(rows):
        want_y, want_s = _recurrence(
            x[r][:, None], dt[r][:, None], seq["a_log"],
            b[r][:, None], c[r][:, None], state[r], 1)
        assert _rel(np.asarray(y)[r], want_y[:, 0]) <= 1e-4
        if live[r]:
            assert _rel(np.asarray(s)[r], want_s) <= 1e-4
        else:
            np.testing.assert_array_equal(np.asarray(s)[r], state[r])


# ----------------------------------------------------------------------
# the latent expert layer alone

D, L, F, FS, E, K = 32, 16, 24, 40, 16, 4


@pytest.fixture(scope="module")
def moe():
    def block(first, held):
        return LatentMoEBlock(dim=D, latent=L, expert_intermediate=F,
                              shared_intermediate=FS, n_experts=E, top_k=K,
                              routed_scale=5.0, held_first=first,
                              held_experts=held, norm_eps=1e-5, dtype=jnp.float32)

    x = jax.random.normal(jax.random.PRNGKey(4), (1, 30, D))
    params = block(0, E).init(jax.random.PRNGKey(5), x)["params"]
    params = {**params, "router_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(6), (E,))}
    return block, x, params


def _shared(params, x):
    u = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * params["norm"]["scale"]
    h = jnp.square(jax.nn.relu(u @ params["shared_up"]["kernel"]))
    return h @ params["shared_down"]["kernel"]


@pytest.mark.parametrize("n_ranks", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(moe, n_ranks):
    """THE SHARE TEST: the routed parts of all ranks (each holding its
    ``E / n_ranks`` experts) plus the shared expert counted ONCE equal the
    uncut layer (every expert held), and nothing else differs."""
    block, x, params = moe
    uncut = block(0, E).apply({"params": params}, x) - x
    per = E // n_ranks
    total = _shared(params, x)
    for rank in range(n_ranks):
        sl = slice(rank * per, (rank + 1) * per)
        share = {**params, "experts_up": params["experts_up"][sl],
                 "experts_down": params["experts_down"][sl]}
        total = total + (block(rank * per, per).apply({"params": share}, x) - x
                         - _shared(params, x))
    np.testing.assert_allclose(total, uncut, atol=1e-5)


def test_relu2_held_layer_is_a_dense_loop_over_pairs():
    """``dropless_held_ffn`` ungated: each (token, choice) pair whose expert
    is held, one at a time, ``w relu(u W_up_e)^2 W_down_e``; padding routes
    nowhere."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    t, held, first = 20, 4, 4
    u = jax.random.normal(ks[0], (t, L))
    ids = jax.random.randint(ks[1], (t, K), 0, E)
    w = jax.random.uniform(ks[2], (t, K))
    up = jax.random.normal(ks[3], (held, L, F)) * L ** -0.5
    down = jax.random.normal(ks[0], (held, F, L)) * F ** -0.5
    valid = jnp.arange(t) < 17
    y, load = ep.dropless_held_ffn(u, ids, w, None, up, down, first, valid)
    want, count = np.zeros((t, L), np.float32), np.zeros(held, np.int64)
    for i in range(17):
        for j in range(K):
            e = int(ids[i, j]) - first
            if 0 <= e < held:
                h = np.square(np.maximum(np.asarray(u[i] @ up[e]), 0))
                want[i] += float(w[i, j]) * (h @ np.asarray(down[e]))
                count[e] += 1
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert load.tolist() == count.tolist()
