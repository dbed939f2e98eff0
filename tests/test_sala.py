"""MiniCPM-SALA's two mixers through the serving engine against the plain
float32 reference (``benchmark/reference_sala.py``: the benchmark's copy IS
the test suite's), at a tiny preset on the CPU with interpreted kernels:
hidden 128, 4 + 4 layers in the 1:3 pattern, ``dense_len`` 64, blocks of 8,
top-4 and a window of 32, so that selection is active from the 65th token.

Tolerances: the tiny model is float32 end to end, like the reference, so
engine and reference differ by reduction order only.  Logits of a random-
init model here are O(1) with top-2 gaps of ~0.7; 1e-3 on a logit or a
log-probability is ~100 times the float32 noise read (1e-5) and ~1000 times
below what a wrong block, a stale state or a missing gate moves.  The
kernels against their recurrences: 1e-4 on values of O(10), float32
reassociation over 300 tokens.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark import reference_sala as ref  # noqa: E402
from benchmark import sala_serve_runner as runner  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.core.generate import _sample_window_core  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.models.causal_lm import CausalLM  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.models.sala import SalaLM  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.ops import sparse_attention as sa  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.ops.lightning_attention import (  # noqa: E402
    lightning_chunk_scan,
    lightning_slopes,
    lightning_step,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.engine import InferenceEngine  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.serving.stats import ServingStats  # noqa: E402
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker  # noqa: E402

TOL = 1e-3
SPEC = sa.SparseSpec(kernel_size=4, kernel_stride=2, block_size=8, init_blocks=1,
                     window_size=32, topk=4, dense_len=64)
MIX = ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn") * 2
SHAPE = {"mixers": MIX, "heads": 4, "heads_kv": 2, "head_dim": 128, "l_heads": 4,
         "eps": 1e-6, "theta": 10000.0, "scale_emb": 12.0,
         "residual": 1.4 / 32 ** 0.5, "logit_div": 0.5,
         "sparse": (4, 2, 8, 1, 32, 4, 64)}
VOCAB = 512


@pytest.fixture(scope="module")
def model_and_params():
    model = SalaLM(num_classes=VOCAB, dim=128, mixer_types=MIX, heads=4,
                   heads_kv=2, head_dim=128, lightning_heads=4, intermediate=256,
                   residual_layers=32, logit_divisor=0.5, sparse=SPEC,
                   dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    # norm scales off 1 and kernels three times flax's, so that a dropped
    # scale, gate or norm shows
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [x * (1 + 0.2 * jax.random.normal(k, x.shape)) if x.ndim == 1 else x * 3
              for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(tree, leaves)


def make_engine(model, params, chunk=16, slots=3, **kw):
    return InferenceEngine(model, params, slots=slots, max_len=256,
                           kv_page_size=8, kv_pages=80, prefill_chunk=chunk,
                           decode_ahead=1, **kw)


def serve(engine, prompts, max_new):
    reqs = [engine.submit(p, max_new=max_new) for p in prompts]
    while engine.has_work:
        engine.step()
    assert all(r.status == "done" for r in reqs)
    return reqs


def against_reference(params, prompt, req):
    g = np.asarray(req.generated, np.int32)
    rows = np.arange(prompt.size - 1, prompt.size - 1 + g.size)
    logits = np.asarray(ref.logits_rows(params, np.concatenate([prompt, g]), rows, SHAPE))
    picked = logits[np.arange(g.size), g]
    logp = picked - np.asarray(jax.nn.logsumexp(logits, axis=-1))
    return (float(np.max(logits.max(-1) - picked)),
            float(np.max(np.abs(logp - np.asarray(req.logprobs)))))


@pytest.fixture(scope="module")
def served(model_and_params):
    """Four requests (three past ``dense_len``, one within it) over three
    slots, so that the fourth reuses a slot another request left."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32) for n in (100, 70, 37, 130)]
    engine = make_engine(model, params)
    engine.prewarm()
    before = engine._compile.snapshot()
    reqs = serve(engine, prompts, 12)
    late = CompileTracker.delta(engine._compile.snapshot(), before)
    return engine, late["n_compiled_programs"], prompts, reqs


@pytest.mark.parametrize("i", range(4))
def test_prefill_and_cached_decode_match_reference_logits(model_and_params, served, i):
    _, params = model_and_params
    _, _, prompts, reqs = served
    gap, err = against_reference(params, prompts[i], reqs[i])
    assert gap <= TOL and err <= TOL, (gap, err)


def test_state_is_reset_on_slot_reuse(served):
    """The fourth request ran in a slot whose state and compressed keys the
    first to finish had left; its logits match the reference (above) only if
    its first chunk started from nothing.  Here: it did reuse a slot, and
    nothing compiled after ``prewarm()``."""
    engine, late_compiles, _, reqs = served
    assert engine.slots == 3 and len(reqs) == 4
    assert late_compiles == 0
    s = engine.stats.summary()
    assert s["prefill_chunk_starts"][0] == 4          # four first chunks
    assert sum(s["prefill_chunk_starts"].values()) == s["n_prefill_chunks"]


def test_counters_follow_the_selection(served):
    engine, _, prompts, _ = served
    s = engine.stats.summary()
    # per decode step, sparse layer (2) and KV head (2): a row past
    # dense_len reads 1 + 4 + 4 = 9 of its blocks, one within it all of them
    per = 2 * 2
    read = live = dense = 0
    for p in prompts:
        for ctx in range(p.size + 1, p.size + 12):      # 11 window steps a request
            blocks = (ctx - 1) // 8 + 1
            live += blocks * per
            read += (blocks if ctx <= 64 else 9) * per
            dense += ctx <= 64
    assert (s["sparse_blocks_read"], s["sparse_blocks_live"], s["dense_len_rows"]) \
        == (read, live, dense)
    assert s["state_rows_total"] == 3
    merged = ServingStats.merge([engine.stats, engine.stats])
    assert merged["sparse_blocks_read"] == 2 * read
    assert merged["prefill_chunk_starts"][0] == 8


def test_chunked_prefill_matches_whole_prompt_prefill(model_and_params, served):
    """The same prompt in chunks of 16 and as ONE chunk of 128: the same
    tokens, and log-probabilities within float32 reassociation."""
    model, params = model_and_params
    _, _, prompts, reqs = served
    whole = serve(make_engine(model, params, chunk=128, slots=1), [prompts[0]], 12)[0]
    assert whole.generated == reqs[0].generated
    assert np.max(np.abs(np.asarray(whole.logprobs) - np.asarray(reqs[0].logprobs))) <= TOL


def test_selected_blocks_equal_the_references():
    """The program's selection arithmetic on the reference's own float32
    queries and keys picks the reference's blocks, query for query."""
    rng = np.random.default_rng(3)
    s_len, hkv, g, d = 160, 2, 2, 128
    q = jnp.asarray(rng.normal(size=(s_len, hkv * g, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s_len, hkv, d)), jnp.float32)
    _, picked = ref._sparse_rows(
        q, k, k, ref.compressed_keys(k, sparse=SHAPE["sparse"]), 0,
        sparse=SHAPE["sparse"])
    # the program's side: compressed keys as the chunk path builds them
    k_ext = jnp.concatenate([jnp.zeros((2, hkv, d)), k])
    kc = sa.compress_keys(k_ext, SPEC)[1:]            # kernel -1 does not exist
    kc = jnp.pad(kc, [(0, s_len // 2 - kc.shape[0]), (0, 0), (0, 0)])
    t = jnp.arange(s_len)
    scores = sa.block_scores(q.reshape(s_len, hkv, g, d), kc, t, SPEC)
    mine = np.asarray(sa.select_blocks(scores, t, SPEC))
    sparse = np.asarray(t) + 1 > SPEC.dense_len
    assert sparse.sum() == s_len - 64
    np.testing.assert_array_equal(mine[sparse], np.asarray(picked)[sparse])
    # the prefill chunk's bitmap (a threshold, no sort) holds the same set
    bitmap = np.asarray(sa.selection_bitmap(scores, t, SPEC))
    for row in (64, 97, 159):
        for h in range(hkv):
            want = {0, *mine[row, h].tolist(), *range(row // 8 - 3, row // 8 + 1)}
            assert set(np.flatnonzero(bitmap[row, h]).tolist()) == want
    assert bitmap[:64].sum(-1).tolist() == [[i // 8 + 1] * hkv for i in range(64)]
    # ties (neighbouring blocks share a kernel, so they are common): the
    # lowest ids at the threshold, as top_k takes them
    tied = jnp.asarray(rng.integers(1, 4, size=(s_len, hkv, 20)), jnp.float32)
    top = np.asarray(sa.select_blocks(tied, t, SPEC))
    bitmap = np.asarray(sa.selection_bitmap(tied, t, SPEC))
    for row in range(64, s_len):
        for h in range(hkv):
            want = {0, *top[row, h].tolist(), *range(row // 8 - 3, row // 8 + 1)}
            assert set(np.flatnonzero(bitmap[row, h]).tolist()) == want
    # and the decode page list holds exactly first + picked + window
    bt = jnp.arange(1, 1 + 32)[None].repeat(s_len, 0)
    pages, lengths, blocks = sa.decode_page_table(scores, t, bt, SPEC)
    row = 150
    want = [0, *mine[row, 0].tolist(), *range(150 // 8 - 3, 150 // 8 + 1)]
    assert np.asarray(blocks)[row, 0, :9].tolist() == want
    assert int(lengths[row, 0]) == 8 * 8 + 150 % 8 + 1
    assert np.asarray(pages)[row, 0, :9].tolist() == [b + 1 for b in want]


@pytest.mark.parametrize("n_valid", [512, 300])
def test_chunk_scan_matches_token_recurrence(n_valid):
    h, t, d = 4, 512, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(ks[i], (h, t, d), jnp.float32) for i in range(3))
    s0 = jax.random.normal(ks[3], (h, d, d), jnp.float32)
    slopes = lightning_slopes(h)
    o, s1 = lightning_chunk_scan(q, k, v, s0, slopes, jnp.int32(n_valid))
    state, outs = s0[None], []
    for i in range(n_valid):
        o_i, state = lightning_step(q[None, :, i], k[None, :, i], v[None, :, i],
                                    state, slopes, jnp.array([True]))
        outs.append(o_i[0])
    assert float(jnp.abs(o[:, :n_valid] - jnp.stack(outs, 1)).max()) <= 1e-4
    assert float(jnp.abs(s1 - state[0]).max()) <= 1e-4
    # a row that is not decoding keeps its state
    _, kept = lightning_step(q[None, :, 0], k[None, :, 0], v[None, :, 0],
                             state, slopes, jnp.array([False]))
    assert bool((kept == state).all())


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_sparse_prefill_kernel_matches_masked_attention(dtype, tol):
    c, nh, hkv, d, ps, start, n_row, n_pages = 128, 4, 2, 128, 8, 64, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (c, nh, d), jnp.float32).astype(dtype)
    pk = jax.random.normal(ks[1], (n_pages, ps, hkv, d), jnp.float32).astype(dtype)
    pv = jax.random.normal(ks[2], (n_pages, ps, hkv, d), jnp.float32).astype(dtype)
    bt = jax.random.permutation(ks[3], jnp.arange(1, n_pages))[:n_row].astype(jnp.int32)
    t = start + jnp.arange(c)
    sel = sa.selection_bitmap(jax.random.uniform(ks[4], (c, hkv, n_row)), t, SPEC)
    assert sel.sum(-1).max() == 9
    o = sa.sparse_prefill_attention(q, sel, pk, pv, bt, jnp.int32(start))
    kk = pk[bt].reshape(n_row * ps, hkv, d).astype(jnp.float32)
    vv = pv[bt].reshape(n_row * ps, hkv, d).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(c, hkv, nh // hkv, d)
    s = jnp.einsum("ckgd,nkd->ckgn", qg, kk) / np.sqrt(d)
    allow = jnp.repeat(sel, ps, axis=-1) & (jnp.arange(n_row * ps) <= t[:, None, None])
    p = jax.nn.softmax(jnp.where(allow[:, :, None, :], s, -1e30), -1)
    want = jnp.einsum("ckgn,nkd->ckgd", p, vv).reshape(c, nh, d)
    assert float(jnp.abs(o.astype(jnp.float32) - want).max()) <= tol


def test_what_a_recurrent_model_cannot_have_is_refused(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="radix prefix sharing is refused"):
        make_engine(model, params, radix_cache=True)
    with pytest.raises(ValueError, match="chunked prefill only"):
        InferenceEngine(model, params, slots=2, max_len=256, kv_page_size=8)
    with pytest.raises(ValueError, match="one chip"):
        make_engine(model, params, speculative="ngram")
    assert make_engine(model, params)._radix is None


def _window_hlo(window, engine):
    s = engine.slots
    z = lambda dt, *sh: jnp.zeros((s, *sh), dt)  # noqa: E731
    return window.lower(
        engine.params, engine.cache, z(jnp.int32), z(bool), z(jnp.float32),
        z(jnp.float32), z(jnp.int32), z(jnp.float32), z(jnp.uint32, 2),
        z(jnp.int32)).as_text()


def test_uniform_model_window_program_is_unchanged():
    """The shared path's guard: an engine over a uniform K/V model lowers
    its decode window to the text of the window as it was before this layer
    kind existed — ``_sample_window_core`` over the engine's decode clone
    and nothing else, built here — so the program depends on nothing SalaLM
    brought: no ``n_valid`` leaf, no state leaf, the same parameters."""
    model = CausalLM(num_classes=64, dim=64, depth=2, heads=4, heads_kv=2,
                     dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(model, params, slots=2, max_len=64, kv_page_size=8,
                             kv_pages=20)
    decode_model = model.clone(page_size=8, paged_one_device=True)

    def _window_impl(params, cache, tok, active, temps, topps, topks, minps,
                     keys, pos):
        return _sample_window_core(
            decode_model, params, cache, tok, active, temps, topps, topks,
            minps, keys, pos, engine.decode_ahead, 64, True, engine.pad_id)

    text = _window_hlo(engine._window, engine)
    assert "n_valid" not in text and "state" not in text
    assert text == _window_hlo(jax.jit(_window_impl, donate_argnums=(1,)), engine)


@pytest.fixture(scope="module")
def observed():
    """The benchmark's check at its tiny preset: the engine built as the
    runner builds it, the check's two requests observed once."""
    data = os.path.join(os.path.dirname(ref.__file__), "tests", "data_sala")
    cell = harness.load_cell(
        harness.load_json(os.path.join(data, "BENCHMARK.json")), "tiny-sala.longdoc",
        data, seed=11, seconds=0.0, trace=False, rehearse=True)
    engine, _ = runner.build_engine(cell, harness.Setup(0.0))
    return cell, engine, runner.observe(engine, cell)


def test_check_reads_selection_state_and_pages_off_the_engine(observed):
    """In float32 the engine's block ids ARE the reference's, its states and
    compressed keys the reference's to rounding, and the pages the device's
    lists name are the pages the host's formula counted."""
    cell, engine, seen = observed
    got = runner.compare(seen, engine.params, cell.config)
    assert got["ok"], got
    assert got["selection_overlap"] == 1.0
    assert got["state_err"] <= 1e-5 >= got["kc_err"]
    # 2 rows x 5 decode steps x 1 sparse layer x 2 KV heads x (1 + 4 + 4) blocks
    assert got["pages_on_device"] == got["pages_counted"] == 180


def _faulty(seen, fault):
    seen = dict(seen)
    if fault == "blocks":    # any other blocks in place of the selected ones
        seen["picked"] = [[(t, np.zeros_like(ids), lens) for t, ids, lens in row]
                          for row in seen["picked"]]
    elif fault in ("state", "kc"):    # a slot-indexing fault: the other row's
        a, b = seen["held"]
        seen["held"] = [{**a, fault: b[fault]}, {**b, fault: a[fault]}]
    elif fault == "pages":
        seen["pages_counted"] += 1
    return seen


@pytest.mark.parametrize("fault,moved", [
    ("blocks", "selection_overlap"), ("state", "state_err"), ("kc", "kc_err"),
    ("pages", "pages_counted"), ("low", "state_err"), ("low-state", "state_err")])
def test_check_refuses(observed, fault, moved):
    """What the check has to refuse, it refuses, by the number that names
    the fault; ``low`` is the control: the reference in the precision below,
    ``low-state`` a bf16 lightning state alone."""
    cell, engine, seen = observed
    sound = runner.compare(seen, engine.params, cell.config)
    got = runner.compare(_faulty(seen, fault), engine.params, cell.config,
                         low={"low": ref.LOW, "low-state": ("state",)}.get(fault, ()))
    assert sound["ok"] and not got["ok"]
    assert got[moved] != sound[moved]
    lim = got["limits"]
    if moved == "selection_overlap":
        assert got[moved] < lim["selection_overlap_min"]
    elif moved in ("state_err", "kc_err"):
        assert got[moved] > lim[moved + "_max"]
