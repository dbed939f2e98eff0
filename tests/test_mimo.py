"""MiMo-V2-Flash's two attention kinds and its held-expert layer through the
serving engine against the plain float32 reference
(``benchmark/reference_mimo.py``: the benchmark's copy IS the test suite's),
at a tiny preset on the CPU with the paged kernel interpreted: hidden 64,
layers ``[global+dense, window, window, global]`` with three expert layers,
a window of 16, 16 experts of which rank 1 of 4 holds experts 4-7, top-4,
K heads of 192 (stored padded to 256) and V heads of 128 as published.

Tolerances: the tiny model is float32 end to end, like the reference, so
engine and reference differ by reduction order only: 1e-3 on a logit or a
log-probability of a model whose top-2 gaps are ~0.5 is ~1000 times the
float32 noise read (1e-6); chosen experts are EQUAL ids; rings agree to 1e-5.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark import mimo_serve_runner as runner  # noqa: E402
from benchmark import reference_mimo as ref  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.ops import paged_attention as pa  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.parallel import expert_parallel as ep  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.serving.engine import InferenceEngine  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.serving.stats import ServingStats  # noqa: E402

TOL = 1e-3
DATA = os.path.join(os.path.dirname(ref.__file__), "tests", "data_mimo")
WINDOW, CHUNK = 16, 32


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(
        harness.load_json(os.path.join(DATA, "BENCHMARK.json")), "tiny-mimo.mixed",
        DATA, seed=11, seconds=0.0, trace=False, rehearse=True)


@pytest.fixture(scope="module")
def model_and_params(cell):
    from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret

    set_interpret(True)
    model = runner.build_model(cell.config, rehearse=True)
    return model, runner.mimo_weights(model, 11, jnp.float32)


def make_engine(model, params, slots=2, **kw):
    kw.setdefault("prefill_chunk", CHUNK)
    return InferenceEngine(model, params, slots=slots, max_len=256,
                           kv_page_size=8, kv_pages=80, decode_ahead=1, **kw)


# prompts under the window, at its edge, over it (the ring wraps), over one
# chunk and over two; six requests on two slots, so that every slot is
# reused, the last time by a prompt shorter than the ring
PROMPTS = (5, WINDOW, 40, 70, 33, 3)
NEW = 20


@pytest.fixture(scope="module")
def served(model_and_params, cell):
    model, params = model_and_params
    engine = make_engine(model, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cell.config["vocab_size"], n).astype(np.int32)
               for n in PROMPTS]
    reqs = [engine.submit(p, max_new=NEW) for p in prompts]
    rings = {}
    while engine.has_work:
        engine.step()
        for i, r in enumerate(reqs):
            # a request's rings as they stand while it still holds its slot
            if r in engine._slot_req and len(r.generated) == NEW - 1:
                slot = engine._slot_req.index(r)
                rings[i] = {k: [np.asarray(e[k][slot]) for e in engine.cache.values()
                                if k in e] for k in ("ring_k", "ring_v")}
    assert all(r.status == "done" for r in reqs)
    engine.sync_expert_load()
    return engine, prompts, reqs, rings


def _reference(cell, params, tokens, rows, audit=None, low=()):
    return np.asarray(ref.logits_rows(params, tokens, rows, ref.shape_of(cell.config),
                                      audit, low=low))


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_prefill_and_cached_decode_match_reference_logits(cell, served, i):
    engine, prompts, reqs, _ = served
    p, g = prompts[i], np.asarray(reqs[i].generated, np.int32)
    at = _reference(cell, engine.params, np.concatenate([p, g]),
                    np.arange(p.size - 1, p.size - 1 + g.size))
    picked = at[np.arange(g.size), g]
    assert float(np.max(at.max(-1) - picked)) <= TOL
    logp = picked - np.asarray(jax.nn.logsumexp(at, axis=-1))
    assert float(np.max(np.abs(logp - np.asarray(reqs[i].logprobs)))) <= TOL


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_rings_hold_the_references_last_window(cell, served, i):
    """After ``fed`` tokens a window layer's ring holds, at slot p mod
    window, the reference's key and value of each of the last ``window``
    positions: under the window, wrapped, and in a slot whose last tenant
    was longer."""
    engine, prompts, reqs, rings = served
    p, g = prompts[i], np.asarray(reqs[i].generated, np.int32)
    fed = p.size + NEW - 2      # captured with NEW - 1 tokens generated
    audit = {"ring_at": fed}
    _reference(cell, engine.params, np.concatenate([p, g]), [0], audit)
    at = np.arange(max(fed - WINDOW, 0), fed) % WINDOW
    for k in ("ring_k", "ring_v"):
        assert len(rings[i][k]) == len(audit[k]) == 2
        for got, want in zip(rings[i][k], audit[k]):
            np.testing.assert_allclose(got[at], want, atol=1e-5)


def test_the_check_reads_equal_experts_rings_and_load(cell, model_and_params):
    """The benchmark's check at its tiny preset: in float32 the engine's
    chosen experts ARE the reference's, its rings the reference's to
    rounding, the device's load the reference's count."""
    model, params = model_and_params
    engine, _ = runner.build_engine(cell, harness.Setup(0.0))
    seen = runner.observe(engine, cell)
    got = runner.compare(seen, engine.params, cell.config)
    assert got["ok"], got
    assert got["expert_overlap"] == 1.0 and got["load_err"] == 0.0
    assert got["ring_err"] <= 1e-5
    assert got["pairs_counted"] == got["pairs_expected"] > 0
    assert got["held_on_device"] == got["held_in_reference"] > 0
    control = runner.compare(seen, engine.params, cell.config, low=ref.LOW)
    assert not control["ok"]
    # float8 weights, a dropped sink and a dropped correction bias each move
    # the check out of a limit by itself (router logits from bf16 operands
    # alone move no choice of these 22 tokens: 16 experts are far apart)
    for low in ("weights", "sink", "bias"):
        alone = runner.compare(seen, engine.params, cell.config, low=(low,))
        assert not alone["ok"], low
    # a slot-indexing fault: the other row's rings
    a, b = seen["rings"]
    swapped = runner.compare({**seen, "rings": [b, a]}, engine.params, cell.config)
    assert not swapped["ok"] and swapped["ring_err"] > got["limits"]["ring_err_max"]


def test_counters_follow_rings_pages_and_experts(cell, served):
    engine, prompts, reqs, _ = served
    s = engine.stats.summary()
    fed = sum(p.size + NEW - 1 for p in prompts)
    assert s["expert_assignments"] == fed * 4 * 3   # top-4, three expert layers
    assert s["expert_assignments_held"] == sum(map(sum, s["expert_load"])) > 0
    assert len(s["expert_load"]) == 3 and len(s["expert_load"][0]) == 4
    assert all(h <= n for hs, ns in zip(s["expert_hits"], s["expert_load"])
               for h, n in zip(hs, ns))
    assert s["ring_rows_total"] == 2
    # every decode step of every row reads ceil(context / 8) pages in each
    # of the two global layers
    pages = sum(-(-(p.size + j) // 8) for p in prompts for j in range(1, NEW))
    assert s["global_pages_read"] == 2 * pages
    assert s["paged_kernel_windows"] == s["n_windows"] > 0
    merged = ServingStats.merge([engine.stats, engine.stats])
    for k in ("expert_assignments", "expert_assignments_held", "global_pages_read"):
        assert merged[k] == 2 * s[k]
    assert merged["expert_load"] == [[2 * n for n in layer] for layer in s["expert_load"]]
    assert merged["expert_hits"] == [[2 * n for n in layer] for layer in s["expert_hits"]]


# ----------------------------------------------------------------------
# the held-expert layer alone

D, F, E, K = 32, 16, 16, 4


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    return {
        "u": jax.random.normal(ks[0], (50, D)),
        "router": jax.random.normal(ks[1], (D, E)) * D ** -0.5,
        "bias": 0.3 * jax.random.normal(ks[2], (E,)),
        "gate": jax.random.normal(ks[3], (E, D, F)) * D ** -0.5,
        "up": jax.random.normal(ks[4], (E, D, F)) * D ** -0.5,
        "down": jax.random.normal(ks[5], (E, F, D)) * F ** -0.5,
    }


def _uncut(layer, ids, w):
    """The whole layer, densely: every expert on every token, weighted."""
    u = layer["u"]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", u, layer["gate"])) * jnp.einsum(
        "td,edf->tef", u, layer["up"])
    y = jnp.einsum("tef,efd->ted", h, layer["down"])
    dense_w = jnp.zeros((u.shape[0], E)).at[jnp.arange(u.shape[0])[:, None], ids].set(w)
    return jnp.einsum("ted,te->td", y, dense_w)


@pytest.mark.parametrize("n_ranks", [1, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(layer, n_ranks):
    """THE SHARE TEST: the partial sums of all ranks (``held`` = each rank's
    experts) add up to the uncut layer's output, and their loads to T x k."""
    ids, w = ep.sigmoid_topk_route(layer["u"], layer["router"], layer["bias"], K)
    per = E // n_ranks
    total, pairs = 0.0, 0
    for rank in range(n_ranks):
        sl = slice(rank * per, (rank + 1) * per)
        y, load = ep.dropless_held_ffn(
            layer["u"], ids, w, layer["gate"][sl], layer["up"][sl],
            layer["down"][sl], rank * per)
        total, pairs = total + y, pairs + int(load.sum())
    assert pairs == layer["u"].shape[0] * K
    np.testing.assert_allclose(total, _uncut(layer, ids, w), atol=1e-5)


def test_dropless_under_forced_imbalance(layer):
    """Every token to ONE held expert (and to three that live elsewhere):
    nothing is lost, whatever the imbalance — no capacity bounds a group."""
    t = layer["u"].shape[0]
    ids = jnp.tile(jnp.asarray([[5, 0, 9, 14]], jnp.int32), (t, 1))
    w = jnp.full((t, K), 0.25)
    y, load = ep.dropless_held_ffn(layer["u"], ids, w, layer["gate"][4:8],
                                   layer["up"][4:8], layer["down"][4:8], 4)
    assert load.tolist() == [0, t, 0, 0]
    h = jax.nn.silu(layer["u"] @ layer["gate"][5]) * (layer["u"] @ layer["up"][5])
    np.testing.assert_allclose(y, 0.25 * (h @ layer["down"][5]), atol=1e-5)
    # tokens that are not real route nowhere
    valid = jnp.arange(t) < 7
    y, load = ep.dropless_held_ffn(layer["u"], ids, w, layer["gate"][4:8],
                                   layer["up"][4:8], layer["down"][4:8], 4, valid)
    assert load.tolist() == [0, 7, 0, 0] and not bool(jnp.any(y[7:]))


def test_correction_bias_moves_the_choice_and_not_the_weights(layer):
    u, w_r = layer["u"], layer["router"]
    ids0, w0 = ep.sigmoid_topk_route(u, w_r, jnp.zeros((E,)), K)
    lift = jnp.zeros((E,)).at[3].set(10.0)      # expert 3 always chosen
    ids1, w1 = ep.sigmoid_topk_route(u, w_r, lift, K)
    assert bool(jnp.all((ids1 == 3).any(-1))) and not bool(jnp.all((ids0 == 3).any(-1)))
    s = jax.nn.sigmoid(u @ w_r)
    np.testing.assert_allclose(w1.sum(-1), 1.0, atol=1e-6)
    # the weights are the SCORES of the chosen, renormalised: no bias in them
    want = jnp.take_along_axis(s, ids1, -1)
    np.testing.assert_allclose(w1, want / want.sum(-1, keepdims=True), atol=1e-5)
    same = jnp.all(jnp.sort(ids0, -1) == jnp.sort(ids1, -1), -1)
    np.testing.assert_allclose(jnp.sort(w0, -1)[same], jnp.sort(w1, -1)[same], atol=1e-6)


# ----------------------------------------------------------------------
# the paged kernel with K rows wider than V rows

PS, N_ROW = 8, 20


def _wide_case(dtype, hkv, g, lengths, dk, dv):
    b = len(lengths)
    pages = [-(-n // PS) for n in lengths]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(hkv), 3)
    q = jax.random.normal(kq, (b, hkv * g, dk), dtype)
    pool_k = jax.random.normal(kk, (1 + sum(pages), PS, hkv, dk), dtype)
    pool_v = jax.random.normal(kv, (1 + sum(pages), PS, hkv, dv), dtype)
    bt = np.zeros((b, N_ROW), np.int32)
    nxt = 1
    for r, n in enumerate(pages):
        bt[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return q, pool_k, pool_v, jnp.asarray(bt), jnp.asarray(lengths, jnp.int32)


def _gather_reference(q, pool_k, pool_v, bt, lengths, scale):
    b, h, dk = q.shape
    hkv = pool_k.shape[2]
    kc = pool_k[bt].reshape(b, -1, hkv, dk).astype(jnp.float32)
    vc = pool_v[bt].reshape(b, -1, hkv, pool_v.shape[-1]).astype(jnp.float32)
    sc = jnp.einsum("bkgd,bnkd->bkgn",
                    q.reshape(b, hkv, h // hkv, dk).astype(jnp.float32), kc) * scale
    live = jnp.arange(kc.shape[1])[None, None, None, :] < lengths[:, None, None, None]
    p = jax.nn.softmax(jnp.where(live, sc, -1e30), -1)
    return jnp.einsum("bkgn,bnkd->bkgd", p, vc).reshape(b, h, -1)


@pytest.mark.parametrize("dtype,hkv,g,dk,dv,tol", [
    (jnp.bfloat16, 4, 16, 256, 128, 2e-2),   # the configuration's global layer
    (jnp.float32, 2, 2, 256, 128, 1e-5),     # the tiny preset's
    (jnp.float32, 1, 4, 256, 256, 1e-5),
    (jnp.bfloat16, 2, 3, 384, 128, 2e-2),
], ids=["bf16-4kv-256/128", "f32-2kv-256/128", "f32-1kv-256/256", "bf16-2kv-384/128"])
def test_kernel_reads_k_rows_wider_than_v_rows(dtype, hkv, g, dk, dv, tol):
    """Against a gather reference on the same pool (interpret mode), ragged
    lengths over three DMA waves, the trash page poisoned; the scale is the
    model's 192, not the stored width's."""
    q, pk, pv, bt, lens = _wide_case(dtype, hkv, g, [1, PS, PS + 1, 61, 130, 159, 2],
                                     dk, dv)
    want = _gather_reference(q, pk, pv, bt, lens, 192 ** -0.5)
    got = pa.paged_decode_attention(q, pk.at[0].set(jnp.nan), pv.at[0].set(jnp.inf),
                                    bt, lens, scale=192 ** -0.5, interpret=True)
    assert got.shape == (len(lens), hkv * g, dv) and got.dtype == q.dtype
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) <= tol
    assert pa.paged_kernel_eligible(dtype, dtype, PS, hkv, dk, dv)


def test_equal_widths_lower_as_they_did_before_the_widths_were_named():
    """The accepted cells' call (K and V rows of 128, no scale named) lowers
    to the same text as a call that names what that call implies: the
    widened signature added nothing to the program of a 128 / 128 pool."""
    q, pk, pv, bt, lens = _wide_case(jnp.bfloat16, 2, 12, [3, 70, 17, 160], 128, 128)

    def text(**kw):
        return jax.jit(lambda *a: pa.paged_decode_attention(*a, interpret=True, **kw)
                       ).lower(q, pk, pv, bt, lens).as_text()

    assert text() == text(scale=128 ** -0.5)
    assert text() != text(scale=192 ** -0.5)


# ----------------------------------------------------------------------
# what the engine refuses, by name

def test_what_a_ring_and_expert_model_cannot_have_is_refused(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="window-ring and expert layers.*chunked prefill only"):
        InferenceEngine(model, params, slots=2, max_len=256, kv_page_size=8)
    with pytest.raises(ValueError, match="radix prefix sharing is refused"):
        make_engine(model, params, radix_cache=True)
    with pytest.raises(ValueError, match="speculative decoding does not compose"):
        make_engine(model, params, speculative="ngram")
    with pytest.raises(ValueError, match="expert banks have no int8 form"):
        make_engine(model, params, quant="int8")
    with pytest.raises(ValueError, match="one chip"):
        make_engine(model, params, tp=2)
    with pytest.raises(ValueError, match="one chip"):
        make_engine(model, params, role="prefill")
    with pytest.raises(ValueError, match="does not compose with the dense prefix cache"):
        make_engine(model, params, prefix_cache_bytes=1 << 20)
    with pytest.raises(ValueError, match="whole windows"):
        make_engine(model, params, prefill_chunk=24).prewarm()
    assert make_engine(model, params)._radix is None
