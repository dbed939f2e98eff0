"""The paged decode-attention kernel (ops/paged_attention.py, ISSUE 26).

* PARITY — through the Pallas interpreter the kernel reproduces the
  gather + ``_attend_cached`` path of models/transformer.py on the SAME
  pool, block tables and lengths.  Tolerances (|got - want| <= atol +
  rtol * |want|): f32 pools 2e-6 + 1e-5 (reduction order only); bf16
  pools 8e-3 + 2 ** -6, two bf16 ulps of the output (the kernel rounds
  UNNORMALISED probabilities to bf16 for the PV product and divides in
  f32 at the end; the gather path rounds the normalised ones).
* LIVE PAGES ONLY — block-table entries past a row's length point at a
  trash page filled with NaN/inf; no result ever holds one.
* ELIGIBILITY — the model takes the kernel only for single-token steps of
  a one-device program over a compute-dtype pool of head dim 128; the
  engine's ``paged_kernel_windows`` says which path its windows took.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.models.transformer import _attend_cached
from distributed_tensorflow_ibm_mnist_tpu.ops import paged_attention as pa
from distributed_tensorflow_ibm_mnist_tpu.serving import InferenceEngine

PS, N_ROW, D = 8, 20, 128  # 20 pages a row: three DMA waves of 8
MAX_LEN = PS * N_ROW
TOL = {jnp.float32: (2e-6, 1e-5), jnp.bfloat16: (8e-3, 2 ** -6)}  # atol, rtol


def _case(dtype, hkv, g, lengths, tables=None, seed=0):
    """q, pools (page 0 = trash), block tables and lengths of one step.
    ``tables`` overrides the default private pages 1.. per row."""
    b = len(lengths)
    pages = [-(-n // PS) for n in lengths]
    n_pages = 1 + sum(pages)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, hkv * g, D), dtype)
    pool_k = jax.random.normal(kk, (n_pages, PS, hkv, D), dtype)
    pool_v = jax.random.normal(kv, (n_pages, PS, hkv, D), dtype)
    bt = np.zeros((b, N_ROW), np.int32)
    nxt = 1
    for r, n in enumerate(pages):
        bt[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    if tables is not None:
        bt = np.asarray(tables, np.int32)
    return (q, pool_k, pool_v, jnp.asarray(bt),
            jnp.asarray(lengths, jnp.int32))


def _gather_path(q, pool_k, pool_v, bt, lengths):
    """What ``_paged_decode_attention`` does without the kernel."""
    b, h, d = q.shape
    hkv = pool_k.shape[2]
    kc = pool_k[bt].reshape(b, MAX_LEN, hkv, d)
    vc = pool_v[bt].reshape(b, MAX_LEN, hkv, d)
    mask = jnp.arange(MAX_LEN)[None, None, :] < lengths[:, None, None]
    return _attend_cached(q[:, None], kc, vc, None, None, mask, q.dtype)[:, 0]


def _poison(pool_k, pool_v):
    return pool_k.at[0].set(jnp.nan), pool_v.at[0].set(jnp.inf)


def _assert_close(got, want):
    atol, rtol = TOL[want.dtype.type]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=atol, rtol=rtol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lengths", [
    [1], [PS], [PS + 1], [1, PS, PS + 1, 61, 130, MAX_LEN - 1, 2],
    [MAX_LEN, MAX_LEN + 7],  # an overrun row: the wrapper clamps to max_len
], ids=["one", "page", "page+1", "ragged", "max_len"])
def test_kernel_matches_gather_path(dtype, lengths):
    """GQA group of 12 (24 query heads on 2 KV heads, the benchmark's)."""
    q, pk, pv, bt, lens = _case(dtype, 2, 12, [min(n, MAX_LEN) for n in lengths])
    want = _gather_path(q, pk, pv, bt, lens)
    # the trash page is poisoned AFTER the reference read its (masked) zeros
    got = pa.paged_decode_attention(
        q, *_poison(pk, pv), bt, jnp.asarray(lengths, jnp.int32),
        interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    _assert_close(got, want)


@pytest.mark.parametrize("dtype,hkv,g", [
    (jnp.bfloat16, 4, 2), (jnp.bfloat16, 8, 1), (jnp.float32, 1, 4),
    (jnp.float32, 4, 1),
], ids=["bf16-4kv", "bf16-8kv", "f32-1kv", "f32-4kv"])
def test_kernel_splits_every_head_layout(dtype, hkv, g):
    """Heads come out of 32-bit rows: strided rows for f32, low/high halves
    of a word for bf16 pairs."""
    q, pk, pv, bt, lens = _case(dtype, hkv, g, [3, 70, 17, 160], seed=hkv)
    want = _gather_path(q, pk, pv, bt, lens)
    got = pa.paged_decode_attention(q, *_poison(pk, pv), bt, lens,
                                    interpret=True)
    _assert_close(got, want)


def test_rows_sharing_prefix_pages():
    """Two rows whose first pages are the SAME pool pages (the radix trie's
    sharing) and whose tails are private; a third row reads them too."""
    q, pk, pv, _, _ = _case(jnp.float32, 2, 12, [PS * 6] * 3)
    tables = np.zeros((3, N_ROW), np.int32)
    tables[0, :5] = [1, 2, 3, 4, 5]
    tables[1, :6] = [1, 2, 3, 9, 10, 11]
    tables[2, :3] = [1, 2, 3]
    lens = jnp.asarray([5 * PS - 2, 6 * PS, 3 * PS], jnp.int32)
    bt = jnp.asarray(tables)
    want = _gather_path(q, pk, pv, bt, lens)
    got = pa.paged_decode_attention(q, *_poison(pk, pv), bt, lens,
                                    interpret=True)
    _assert_close(got, want)


@pytest.mark.parametrize("compute,pool,ps,hkv,d,want", [
    (jnp.bfloat16, jnp.bfloat16, 64, 2, 128, True),   # the benchmark's
    (jnp.float32, jnp.float32, 8, 1, 128, True),
    (jnp.bfloat16, jnp.int8, 64, 2, 128, False),      # int8 KV + scales
    (jnp.float32, jnp.bfloat16, 64, 2, 128, False),   # pool != compute
    (jnp.bfloat16, jnp.bfloat16, 64, 1, 128, False),  # no pair to pack
    (jnp.float32, jnp.float32, 8, 3, 128, False),     # XLA pads 3 rows to 4
    (jnp.bfloat16, jnp.bfloat16, 64, 16, 128, False),  # 8 rows: not tried
    (jnp.float32, jnp.float32, 8, 4, 16, False),      # tier-1's tiny heads
    (jnp.float32, jnp.float32, 4, 2, 128, False),     # page < a sublane tile
])
def test_eligibility_is_shapes_and_dtypes(compute, pool, ps, hkv, d, want):
    assert pa.paged_kernel_eligible(compute, pool, ps, hkv, d) is want


# ----------------------------------------------------------------------
# through the model and the engine

PROMPTS = [[1, 2, 3, 4, 5], [7, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
           [3, 1, 4, 1, 5, 9, 2, 6], [6, 6, 6]]


def _model_and_params(**over):
    # head dim 128 (dim 512 / 4 heads), GQA 2:1 — the smallest eligible LM
    kw = dict(num_classes=16, dim=512, depth=2, heads=4, heads_kv=2,
              mlp_ratio=1, dtype=jnp.float32)
    model = get_model("causal_lm", **{**kw, **over})
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _serve(model, params, **kw):
    eng = InferenceEngine(model, params, slots=3, max_len=32, **kw)
    reqs = [eng.submit(p, max_new=8) for p in PROMPTS]
    eng.run()
    return [(r.status, tuple(r.generated)) for r in reqs], eng.stats


def test_engine_kernel_windows_match_gather_tokens():
    """An eligible engine decodes every window through the kernel and its
    greedy tokens equal the dense engine's (which the gather path equals,
    tests/test_kv_paging.py) — and the gather path's itself at tp=2."""
    model, params = _model_and_params()
    want, _ = _serve(model, params)
    got, stats = _serve(model, params, kv_page_size=8, radix_cache=False)
    assert got == want
    s = stats.summary()
    assert s["n_windows"] > 0
    assert s["paged_kernel_windows"] == s["n_windows"]
    assert stats.vitals()["paged_kernel_windows"] == s["n_windows"]


def test_engine_radix_shared_pages_through_kernel():
    """Shared prefix pages stay read-only under the kernel: repeated
    prompts served from the trie decode the same tokens."""
    model, params = _model_and_params()
    prompts = [list(range(1, 13)), list(range(1, 13)) + [3],
               list(range(1, 13))]

    def run(**kw):
        eng = InferenceEngine(model, params, slots=2, max_len=32, **kw)
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        eng.run()
        return [tuple(r.generated) for r in reqs], eng.stats.summary()

    want, _ = run()
    got, s = run(kv_page_size=8, radix_cache=True)
    assert got == want
    assert s["radix_hits"] > 0
    assert s["paged_kernel_windows"] == s["n_windows"] > 0


def test_idle_rows_restart_with_every_retirement():
    """The kernel reads as many (trash-page) positions for an idle row as
    its cursor says, and an idle row's cursor counts one garbage token a
    window: so every row nobody holds is zeroed in the reset dispatch a
    retirement makes anyway.  One long request beside short ones, one
    after another: the slot that was never used stays within a short
    request's life of zero, the long row keeps its own cursor, tokens are
    the dense engine's."""
    model, params = _model_and_params()

    def run(**kw):
        eng = InferenceEngine(model, params, slots=3, max_len=32, **kw)
        long = eng.submit([1, 2, 3], max_new=24)
        shorts, idle_max = [], 0
        while eng.has_work:
            if long.status != "done" and (
                    not shorts or shorts[-1].status == "done"):
                shorts.append(eng.submit([4, 5], max_new=3))
            eng.step()
            if kw:
                index = np.asarray(next(iter(eng.cache.values()))["index"])
                idle_max = max(idle_max, int(index[2]))
        assert long.status == "done" and all(r.status == "done" for r in shorts)
        return [tuple(r.generated) for r in [long] + shorts], idle_max, eng

    want, _, _ = run()
    got, idle_max, eng = run(kv_page_size=8, radix_cache=False)
    assert got == want
    s = eng.stats.summary()
    assert s["paged_kernel_windows"] == s["n_windows"] > 20
    # slot 2 never held a request: without the restart its cursor stands at
    # the number of windows run (over 20), with it at the windows since the
    # last short request retired
    assert idle_max <= 6


@pytest.mark.parametrize("case", ["int8", "tp2", "spec"])
def test_ineligible_engines_keep_the_gather_path(case):
    """int8 KV, a tp mesh and speculative verify windows read 0 and decode
    what they decode today."""
    over, ekw = {}, {}
    if case == "int8":
        over = {"kv_cache_dtype": "int8"}
    elif case == "tp2":
        ekw = {"tp": 2}
    else:
        ekw = {"speculative": "ngram"}
    model, params = _model_and_params(**over)
    want, _ = _serve(model, params, **ekw)
    got, stats = _serve(model, params, kv_page_size=8, radix_cache=False,
                        **ekw)
    assert got == want
    s = stats.summary()
    assert s["n_windows"] > 0 and s["paged_kernel_windows"] == 0


def test_counter_is_exact_through_merge():
    from distributed_tensorflow_ibm_mnist_tpu.serving.stats import ServingStats

    a, b = ServingStats(2), ServingStats(2)
    for _ in range(3):
        a.window(0.0, 0.0, steps=2, waste=0, paged_kernel=True)
    b.window(0.0, 0.0, steps=2, waste=0)
    merged = ServingStats.merge([a, b])
    assert merged["n_windows"] == 4 and merged["paged_kernel_windows"] == 3


def test_multi_token_chunks_keep_the_gather_path():
    """s > 1 (suffix extend, chunked prefill, verify) never reaches the
    kernel, even on the one-device clone."""
    model, params = _model_and_params()
    paged = model.clone(page_size=8, paged_one_device=True)
    from distributed_tensorflow_ibm_mnist_tpu.serving.kv_pool import init_paged_cache

    cache = init_paged_cache(model, params, 2, 32, 8, 9)

    def jaxpr(m, s):
        return str(jax.make_jaxpr(lambda p, c, t: m.apply(
            {"params": p, "cache": c}, t, decode=True, max_len=32,
            ragged=True, mutable=["cache"]))(
                params, cache, jnp.zeros((2, s), jnp.int32)))

    assert "pallas_call" in jaxpr(paged, 1)
    assert "pallas_call" not in jaxpr(paged, 4)
    # a clone the engine did not vouch for keeps the gather
    assert "pallas_call" not in jaxpr(model.clone(page_size=8), 1)


# ----------------------------------------------------------------------
# the chip's compiler, without the chip (on-chip-measurement guide, §2)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_v5e_compiles_kernel_without_copying_the_pool(one_chip):
    """The benchmark's widths (64 slots, 2816 pages of 64 tokens, 24 heads
    on 2 KV heads of 128) inside a jit that scatters the step's K/V into
    the donated pool first, as the decode window does: Mosaic accepts the
    pool as stored, and XLA puts no pool-sized ``copy`` before it."""
    b, h, hkv, ps, n_row, n_pages = 64, 24, 2, 64, 64, 2816

    def step(pool_k, pool_v, q, k, v, bt, idx):
        page = jnp.take_along_axis(bt, (idx // ps)[:, None], axis=1)[:, 0]
        pool_k = pool_k.at[page, idx % ps].set(k)
        pool_v = pool_v.at[page, idx % ps].set(v)
        o = pa.paged_decode_attention(q, pool_k, pool_v, bt, idx + 1,
                                      interpret=False)
        return pool_k, pool_v, o

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((n_pages, ps, hkv, D), jnp.bfloat16)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        pool, pool, s((b, h, D), jnp.bfloat16), s((b, hkv, D), jnp.bfloat16),
        s((b, hkv, D), jnp.bfloat16), s((b, n_row), jnp.int32),
        s((b,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    pool_shape = f"bf16[{n_pages},{ps},{hkv},{D}]"
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and ln.split("=")[1].lstrip().startswith(pool_shape)]
    assert not copies, copies
