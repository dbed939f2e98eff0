"""Paged KV cache + radix prefix sharing (serving/kv_pool.py,
serving/radix_cache.py, the engine's ``kv_page_size=`` path — ISSUE 7).

The decisive properties:

* PARITY — greedy decode through the PAGED engine (pool + block tables +
  gather/scatter attention) is token-for-token identical to the dense
  engine for every ``decode_ahead``, under mixed retirement (EOS / budget
  / deadline), and with ``kv_cache_dtype="int8"`` quantized pages.
* SHARING — the radix trie serves repeated prompt prefixes from shared
  refcounted pages (prefill compute skipped for the match), with output
  still dense-identical; divergence never corrupts a shared page (COW by
  block-table remapping).
* OVERCOMMIT — a pool smaller than ``slots * max_len`` stalls admission
  when dry (never fails, never corrupts) and every request still
  completes, with identical tokens.
* ACCOUNTING — pages drain back to the pool at retirement; ServingStats'
  page/radix fields are strict-JSON-safe; chaos per-site event counts are
  unchanged by the cache layout (paging is invisible to fault schedules).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.generate import make_prefill
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    KVPagePool,
    PrefixCache,
    RadixCache,
    ServingStats,
    init_paged_cache,
    pages_needed,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.kv_pool import (
    make_paged_insert,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)

KW = dict(num_classes=16, dim=64, depth=2, heads=4, dtype=jnp.float32)

PROMPTS = [
    [1, 2, 3, 4, 5],
    [7, 8],
    [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
    [3, 1, 4, 1, 5, 9, 2, 6],
    [2, 7, 1, 8],
    [6, 6, 6],
]


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _run(engine, prompts=PROMPTS, max_new=10, **submit_kw):
    reqs = [engine.submit(p, max_new=max_new, **submit_kw) for p in prompts]
    engine.run()
    return reqs


def _outputs(reqs):
    return [(r.status, tuple(r.generated)) for r in reqs]


# ----------------------------------------------------------------------
# host-side units: page pool + radix trie


def test_page_pool_alloc_free():
    pool = KVPagePool(n_pages=6, page_size=8)
    assert pool.capacity == 5 and pool.free_count == 5 and pool.allocated == 0
    a = pool.alloc(3)
    assert a == [1, 2, 3]  # ascending, page 0 (trash) never handed out
    assert pool.alloc(3) is None  # all-or-nothing: nothing was taken
    assert pool.free_count == 2
    pool.free([2])
    b = pool.alloc(3)
    assert sorted(b) == [2, 4, 5] and pool.free_count == 0
    with pytest.raises(ValueError, match="invalid page id"):
        pool.free([0])  # the trash page is not freeable
    with pytest.raises(ValueError, match="invalid page id"):
        pool.free([6])
    pool.free([1, 3] + b)  # pages 1, 3 from `a` (2 was already returned)
    with pytest.raises(ValueError, match="double free"):
        pool.free([1])


def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(33, 8) == 5


def test_radix_trie_match_insert_evict():
    rc = RadixCache(page_size=4)
    toks = np.arange(12, dtype=np.int32)
    path, m = rc.match(toks)
    assert path == [] and m == 0
    held, kept = rc.insert(toks, 0, {0: 5, 1: 6, 2: 7}, [])
    assert [n.page for n in held] == [5, 6, 7] and kept == []
    assert rc.n_blocks == 3
    # full and partial matches
    path, m = rc.match(toks)
    assert m == 12 and [n.page for n in path] == [5, 6, 7]
    path, m = rc.match(np.asarray([0, 1, 2, 3, 9, 9, 9, 9], np.int32))
    assert m == 4 and [n.page for n in path] == [5]
    # duplicate insert: existing node wins, the donor keeps its page
    held2, kept2 = rc.insert(toks[:8], 1, {1: 9}, rc.match(toks[:4])[0])
    assert held2 == [] and kept2 == [9]
    # eviction only touches ref==0 LEAF nodes, deepest-LRU first
    rc.release(held)  # drop the donor's refs
    freed = []
    assert rc.evict(1, freed.append) == 1 and freed == [7]
    assert rc.n_blocks == 2
    rc.acquire(rc.match(toks[:4])[0])
    # page 6's node is a leaf with ref 0; page 5's is held -> only 6 frees
    assert rc.evict(5, freed.append) == 1 and freed == [7, 6]
    with pytest.raises(ValueError, match="unheld"):
        rc.release([RadixCache(4).root])


# ----------------------------------------------------------------------
# engine parity: paged == dense, greedily, token for token


@pytest.mark.parametrize("k", [1, 4, 8])
def test_paged_greedy_matches_dense(k):
    model, params = _model_and_params()
    dense = InferenceEngine(model, params, slots=3, max_len=32,
                            decode_ahead=k)
    want = _outputs(_run(dense))
    paged = InferenceEngine(model, params, slots=3, max_len=32,
                            decode_ahead=k, kv_page_size=8,
                            radix_cache=False)
    got = _outputs(_run(paged))
    assert got == want
    s = paged.stats.summary()
    assert s["kv_page_size"] == 8 and s["kv_pages_peak"] > 0


def test_paged_mixed_retirement_matches_dense():
    """EOS, budget, and deadline retirement interleaved mid-window — the
    layouts must agree on every status and every kept token."""
    model, params = _model_and_params(seed=2)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12, 13, 14]]

    def drive(**kw):
        clock = _FakeClock()
        eng = InferenceEngine(model, params, slots=2, max_len=32, eos_id=2,
                              decode_ahead=4, clock=clock, **kw)
        reqs = [eng.submit(prompts[0], max_new=12),
                eng.submit(prompts[1], max_new=3),
                eng.submit(prompts[2], max_new=12, deadline_s=2.0),
                eng.submit(prompts[3], max_new=6)]
        while eng.has_work:
            eng.step()
            clock.t += 1.0  # the deadline request dies mid-flight
        eng.run()
        return _outputs(reqs)

    want = drive()
    got = drive(kv_page_size=8, radix_cache=False)
    assert got == want
    assert any(st == "cancelled" for st, _ in got)  # the deadline fired
    assert any(st == "done" for st, _ in got)


@pytest.mark.parametrize("radix", [False, True])
def test_paged_int8_matches_dense_int8(radix):
    """int8-quantized pages (payload + per-position scales) reproduce the
    dense int8 engine exactly, with and without radix sharing."""
    model, params = _model_and_params(kv_cache_dtype="int8")
    dense = InferenceEngine(model, params, slots=3, max_len=32)
    want = _outputs(_run(dense))
    paged = InferenceEngine(model, params, slots=3, max_len=32,
                            kv_page_size=8, radix_cache=radix)
    got = _outputs(_run(paged))
    assert got == want


def test_int8_scales_reset_on_slot_reuse():
    """Satellite: ragged serving with int8 must reset the SCALE leaves like
    the payload when a slot retires and is reused — more requests than
    slots forces reuse, and outputs must match a no-reuse engine, on both
    layouts."""
    model, params = _model_and_params(kv_cache_dtype="int8")
    fresh = InferenceEngine(model, params, slots=len(PROMPTS), max_len=32)
    want = _outputs(_run(fresh))
    for kw in ({}, {"kv_page_size": 8, "radix_cache": False}):
        reused = InferenceEngine(model, params, slots=2, max_len=32, **kw)
        got = _outputs(_run(reused))
        assert got == want, f"slot-reuse divergence under {kw or 'dense'}"


# ----------------------------------------------------------------------
# landing: the insert writes the pages under the prompt, and only those

_KV = {"bf16": {}, "int8": {"kv_cache_dtype": "int8"}}


@pytest.mark.parametrize("n_tok", [1, 7, 8, 9, 32])
@pytest.mark.parametrize("kv", list(_KV))
def test_insert_writes_the_pages_under_the_cursor(kv, n_tok):
    """``make_paged_insert`` on a pool full of a last tenant's bytes: every
    position below the cursor, read back through the block table, is the
    prefilled row's bit for bit (payload and int8 scales); no page but the
    ``ceil(n_tok / page_size)`` under the cursor changes (the pages the
    row owns for its answer included); the slot's block table and cursor
    are the row's, the other slots' untouched."""
    ps, max_len, n_pages, slots, slot, bucket = 8, 64, 24, 3, 1, 32
    model, params = _model_and_params(dtype=jnp.bfloat16, **_KV[kv])
    rng = np.random.default_rng(n_tok)
    before = jax.tree.map(
        lambda x: jnp.asarray(rng.integers(1, 100, x.shape), x.dtype),
        init_paged_cache(model, params, slots, max_len, ps, n_pages))
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :n_tok] = rng.integers(1, KW["num_classes"], n_tok)
    row, _ = make_prefill(model, max_len)(
        params, jnp.asarray(prompt), jnp.asarray([n_tok]))
    owned = rng.permutation(np.arange(1, n_pages))[
        : pages_needed(n_tok + 20, ps)]  # the prompt and a 20-token answer
    bt_row = np.zeros((max_len // ps,), np.int32)  # rest = TRASH
    bt_row[: owned.size] = owned
    after = jax.jit(make_paged_insert(ps, max_len))(
        before, row, jnp.asarray(bt_row), jnp.asarray(slot, jnp.int32))

    live = bt_row[: pages_needed(n_tok, ps)]
    others = np.setdiff1d(np.arange(1, n_pages), live)
    names = {"pages_k": "k", "pages_v": "v"}
    if kv == "int8":
        names.update(pages_k_scale="k_scale", pages_v_scale="v_scale")
    for name, entry in after.items():
        assert set(names) == {k for k in entry if k.startswith("pages_")}
        for key, row_key in names.items():
            got, want = np.asarray(entry[key]), np.asarray(row[name][row_key])
            assert got.dtype == want.dtype
            span = got[bt_row].reshape((max_len,) + got.shape[2:])
            np.testing.assert_array_equal(span[:n_tok], want[0, :n_tok])
            np.testing.assert_array_equal(
                got[others], np.asarray(before[name][key])[others])
        bt, idx = np.asarray(entry["block_table"]), np.asarray(entry["index"])
        np.testing.assert_array_equal(bt[slot], bt_row)
        assert idx[slot] == n_tok
        rest = np.arange(slots) != slot
        np.testing.assert_array_equal(
            bt[rest], np.asarray(before[name]["block_table"])[rest])
        np.testing.assert_array_equal(
            idx[rest], np.asarray(before[name]["index"])[rest])


@pytest.mark.parametrize("kv", list(_KV))
def test_shorter_tenant_of_a_freed_page_matches_fresh_engine(kv):
    """A pool of exactly one long request's pages: the short request that
    follows it can only be given pages the long one filled, and its insert
    writes one of them.  The long tenant's bytes above the short cursor
    never show: greedy tokens equal a fresh engine's, and the counters say
    how many pages each landing wrote."""
    model, params = _model_and_params(**_KV[kv])
    long_p, short_p = list(range(1, 15)) + [3, 1, 4, 1, 5, 9], [2, 7, 1]
    kw = dict(slots=1, max_len=32, kv_page_size=8, radix_cache=False)
    reused = InferenceEngine(model, params, kv_pages=5, **kw)
    assert reused._pool.capacity == pages_needed(len(long_p) + 10, 8)
    got = _outputs(_run(reused, [long_p, short_p]))
    fresh = InferenceEngine(model, params, **kw)
    assert got[1] == _outputs(_run(fresh, [short_p]))[0]
    assert got[1][0] == "done" and len(got[1][1]) == 10
    s = reused.stats.summary()
    assert (s["insert_rows"], s["insert_pages_written"]) == (2, 3 + 1)


def test_insert_counters_in_summary_and_merge():
    """``insert_rows`` / ``insert_pages_written``: one landing through the
    insert program and the pages under its prompt, exact through
    ``merge``; a radix hit lands through extend and counts nothing; the
    dense engine and an empty record read 0."""
    model, params = _model_and_params()
    eng = InferenceEngine(model, params, slots=3, max_len=32, kv_page_size=8,
                          radix_cache=False)
    _run(eng)
    s = eng.stats.summary()
    assert s["insert_rows"] == len(PROMPTS)
    assert s["insert_pages_written"] == sum(
        pages_needed(len(p), 8) for p in PROMPTS)
    shared = list(range(1, 17))
    radix = InferenceEngine(model, params, slots=1, max_len=32,
                            kv_page_size=8, radix_cache=True)
    _run(radix, [shared + [3], shared + [5, 6]], max_new=4)
    r = radix.stats.summary()
    assert r["radix_hits"] == 1
    assert (r["insert_rows"], r["insert_pages_written"]) == (1, 3)
    dense = InferenceEngine(model, params, slots=2, max_len=32)
    _run(dense, PROMPTS[:2])
    empty = ServingStats(slots=1)
    for rec in (dense.stats, empty):
        z = rec.summary()
        assert z["insert_rows"] == z["insert_pages_written"] == 0
    merged = ServingStats.merge([eng.stats, radix.stats, empty])
    assert merged["insert_rows"] == s["insert_rows"] + 1
    assert merged["insert_pages_written"] == s["insert_pages_written"] + 3
    json.dumps(merged, allow_nan=False)


# ----------------------------------------------------------------------
# radix sharing: shared prefixes, partial hits, COW at divergence


def test_radix_sharing_matches_dense():
    """A shared-system-prompt workload: the radix engine must emit
    dense-identical tokens while serving the shared pages once."""
    model, params = _model_and_params(seed=3)
    shared = [11, 12, 13, 14, 15, 1, 2, 3]          # exactly one page
    prompts = [shared + [i] for i in range(5)]       # diverge after it
    prompts.append(shared[:4] + [9, 9])              # partial-prefix miss
    dense = InferenceEngine(model, params, slots=2, max_len=32)
    want = _outputs(_run(dense, prompts, max_new=6))
    eng = InferenceEngine(model, params, slots=2, max_len=32, kv_page_size=8)
    reqs = _run(eng, prompts, max_new=6)
    assert _outputs(reqs) == want
    s = eng.stats.summary()
    assert s["radix_hits"] >= 3  # later admissions matched the shared page
    assert s["radix_hit_tokens"] == s["radix_hits"] * 8
    assert [r.radix_tokens for r in reqs][0] == 0  # the first paid prefill


def test_radix_pool_drains_after_run():
    """Retirement returns every private page; only trie-resident blocks
    (ref 0, evictable) may remain allocated."""
    model, params = _model_and_params()
    eng = InferenceEngine(model, params, slots=2, max_len=32, kv_page_size=8)
    _run(eng)
    assert eng._pool.allocated == eng._radix.n_blocks
    # with sharing off the pool drains to exactly zero
    eng2 = InferenceEngine(model, params, slots=2, max_len=32,
                           kv_page_size=8, radix_cache=False)
    _run(eng2)
    assert eng2._pool.allocated == 0


def test_overcommit_stalls_then_completes():
    """A pool that cannot hold every slot's worst case (overcommit) must
    serve the full workload anyway — admission stalls while dry, resumes
    as decode frees pages, and tokens stay dense-identical."""
    model, params = _model_and_params()
    dense = InferenceEngine(model, params, slots=4, max_len=32)
    want = _outputs(_run(dense))
    # 4 slots x 4 pages/slot worst case = 16; give it 8 (+ trash)
    eng = InferenceEngine(model, params, slots=4, max_len=32,
                          kv_page_size=8, kv_pages=9, radix_cache=False)
    reqs = _run(eng)
    assert _outputs(reqs) == want
    assert all(r.status == "done" for r in reqs)
    assert eng.stats.summary()["kv_pages_peak"] <= 8


# ----------------------------------------------------------------------
# construction contracts


def test_paged_constructor_validation():
    model, params = _model_and_params()
    with pytest.raises(ValueError, match="multiple of kv_page_size"):
        InferenceEngine(model, params, slots=2, max_len=30, kv_page_size=8)
    with pytest.raises(ValueError, match="needs the paged cache"):
        InferenceEngine(model, params, slots=2, max_len=32, radix_cache=True)
    with pytest.raises(ValueError, match="needs kv_page_size"):
        InferenceEngine(model, params, slots=2, max_len=32, kv_pages=4)
    with pytest.raises(ValueError, match="cannot hold one full-length"):
        InferenceEngine(model, params, slots=2, max_len=32,
                        kv_page_size=8, kv_pages=3)


# ----------------------------------------------------------------------
# accounting: stats schema, oversized counter, chaos invariance


def test_paged_stats_json_safe():
    model, params = _model_and_params()
    eng = InferenceEngine(model, params, slots=2, max_len=32, kv_page_size=8)
    _run(eng)
    s = eng.stats.summary()
    for key in ("kv_page_size", "kv_pages_total", "kv_pages_live",
                "kv_pages_peak", "kv_bytes_live", "kv_bytes_peak",
                "radix_hits", "radix_misses", "radix_hit_tokens",
                "radix_hit_rate"):
        assert key in s, key
    json.dumps(s, allow_nan=False)  # strict-JSON-safe (no NaN/Inf leaks)
    assert s["kv_pages_total"] == 8  # slots * max_len/ps (trash excluded)
    assert s["kv_bytes_peak"] == s["kv_pages_peak"] * eng._page_bytes
    # the dense engine reports the same schema, nulled/zeroed
    dense = InferenceEngine(model, params, slots=2, max_len=32)
    _run(dense)
    sd = dense.stats.summary()
    assert sd["kv_page_size"] is None and sd["kv_pages_peak"] == 0
    json.dumps(sd, allow_nan=False)


def test_prefix_cache_oversized_counter():
    """Satellite: an entry bigger than the whole budget is refused AND
    counted — sizing bugs surface in stats instead of silently thrashing
    the LRU."""
    cache = PrefixCache(max_bytes=64)
    row = {"k": np.zeros((1, 128), np.float32)}  # 512B > 64B budget
    cache.put("a", row, 3)
    assert len(cache) == 0 and cache.bytes == 0 and cache.oversized == 1
    cache.put("b", row, 4)
    assert cache.oversized == 2
    # the engine folds the counter into its stats record
    model, params = _model_and_params()
    eng = InferenceEngine(model, params, slots=2, max_len=32,
                          prefix_cache_bytes=8)  # every row is oversized
    _run(eng, PROMPTS[:3])
    assert eng.stats.summary()["prefix_oversized"] == 3


def test_chaos_event_counts_paging_invariant():
    """The fault-injection contract: per-site event indices depend on the
    request stream, not the cache layout — a seeded plan replays
    identically against dense and paged engines."""
    model, params = _model_and_params()
    counts = {}
    for name, kw in (("dense", {}),
                     ("paged", {"kv_page_size": 8})):
        inj = FaultInjector(FaultPlan())  # count events, fire nothing
        eng = InferenceEngine(model, params, slots=2, max_len=32,
                              chaos=inj, **kw)
        _run(eng)
        counts[name] = {s: inj.events(s)
                        for s in ("serving-admit", "serving-step",
                                  "serving-callback")}
    assert counts["paged"] == counts["dense"]
    assert counts["dense"]["serving-admit"] == len(PROMPTS)


def test_chaos_admit_poison_isolated_on_paged():
    """An injected admission poison on the paged engine fails only its
    request and leaks no pages."""
    model, params = _model_and_params()
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-admit", kind="poison", at=(1,)),)))
    eng = InferenceEngine(model, params, slots=2, max_len=32,
                          kv_page_size=8, radix_cache=False, chaos=inj)
    reqs = _run(eng, PROMPTS[:4])
    assert [r.status for r in reqs] == ["done", "failed", "done", "done"]
    assert eng._pool.allocated == 0  # every page came back


# ----------------------------------------------------------------------
# the memory model: sessions at a fixed number of KV bytes


def test_sessions_at_equal_kv_bytes_and_gqa_page_bytes():
    """A dense engine's concurrency is an allocation: every slot owns
    ``max_len`` positions, so a KV budget buys ``budget / (max_len x token
    bytes)`` sessions.  The same bytes cut into pages (4 dense slots x 128
    positions = 32 pages of 16, + the trash page), behind 16 slots with the
    radix trie holding a 48-token shared system prompt ONCE, hold at least
    TWICE the sessions at once, and no token moves.  With ``heads_kv =
    heads // 4`` a request pins as many pages, each a quarter the bytes."""
    vocab, heads, max_len, page, dense_slots = 64, 4, 128, 16, 4
    kv_pages = dense_slots * max_len // page + 1
    rng = np.random.default_rng(7)
    shared = rng.integers(1, vocab, size=48).tolist()
    prompts = [shared + rng.integers(1, vocab, size=8).tolist()
               for _ in range(12)]

    lm = dict(num_classes=vocab, dim=48, depth=2, heads=heads)
    mha = _model_and_params(**lm)
    gqa = _model_and_params(**lm, heads_kv=heads // 4)

    def serve(model_and_params, **kw):
        eng = InferenceEngine(*model_and_params, max_len=max_len,
                              buckets=(64, 128), eos_id=None, **kw)
        kv_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.cache))
        reqs = [eng.submit(p, max_new=8) for p in prompts]
        peak = 0
        while eng.has_work:
            eng.step()
            peak = max(peak, sum(r is not None for r in eng._slot_req))
        assert all(r.status == "done" for r in reqs)
        summary = eng.stats.summary()
        eng.close()
        return _outputs(reqs), peak, kv_bytes, summary

    paged_kw = dict(slots=4 * dense_slots, kv_page_size=page,
                    kv_pages=kv_pages)
    dense_out, dense_peak, dense_bytes, _ = serve(mha, slots=dense_slots)
    paged_out, paged_peak, paged_bytes, paged = serve(mha, **paged_kw)
    assert paged_out == dense_out
    assert 0.9 <= paged_bytes / dense_bytes <= 1.1   # the budget was fixed
    assert dense_peak == dense_slots and paged_peak >= 2 * dense_peak
    assert paged["radix_hit_tokens"] > 0

    gqa_dense_out, _, _, _ = serve(gqa, slots=dense_slots)
    gqa_out, _, _, gqa_stats = serve(gqa, **paged_kw)
    assert gqa_out == gqa_dense_out
    assert gqa_stats["kv_pages_total"] == paged["kv_pages_total"]
    assert paged["kv_bytes_peak"] / gqa_stats["kv_bytes_peak"] >= 0.9 * 4


def test_close_fails_overcommit_stalled_request_and_frees_pages():
    """Satellite fix (ISSUE 8): close() with a request PARKED on a dry
    page pool (overcommit stall — accepted, prefilled once, starved of
    pages) must fail it TERMINALLY: status ``failed`` with an error
    naming the stall, ``engine_fault`` set (the engine gave up on work it
    had accepted — a router re-dispatches exactly these), every page
    freed, and nothing left parked.  A queued-never-admitted request
    still reads plain ``cancelled``."""
    model, params = _model_and_params()
    # 2 slots but a pool holding ONE full-length request: the second
    # admission prefills, finds the pool dry, and parks
    eng = InferenceEngine(model, params, slots=2, max_len=16, kv_page_size=4,
                          kv_pages=5, radix_cache=False,
                          scheduler=FIFOScheduler(max_len=16, buckets=(8,)))
    r1 = eng.submit([1, 2, 3], max_new=12)
    r2 = eng.submit([4, 5, 6], max_new=12)
    eng.step()
    assert r1.status == "running" and r2.status == "queued"
    assert len(eng._pending) == 1  # r2 parked on the dry pool

    eng.close()
    assert r1.status == "cancelled" and r1.engine_fault
    assert r2.status == "failed" and r2.engine_fault
    assert "overcommit-stalled" in (r2.error or "")
    assert eng._pool.allocated == 0 and not eng._pending
    assert len(eng.scheduler) == 0
    # both surfaced exactly once through the terminal stream
    assert {r.id for r in eng.completed} == {r1.id, r2.id}
