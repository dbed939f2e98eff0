"""Tensor-parallel serving (ISSUE 10): sharding is invisible in the tokens.

The decisive properties:

* PARITY — a curated slice of the composition matrix ({dense, paged} x
  {native, int8 KV} x decode_ahead ∈ {1, 8} x {plain, speculative}) at
  tp ∈ {2, 4} is token-identical to the same config at tp=1: GSPMD
  partitioning (Megatron column/row splits + the KV head-axis shard)
  changes what each chip holds, never what the model says.
* MEMORY — per-chip weight and KV bytes land at ~1/tp of the tp=1
  figure in BOTH cache layouts, and ``ServingStats`` carries
  tp/kv_bytes_per_chip/weight_bytes_per_chip through ``merge`` into the
  router rollup (strict JSON: None, never NaN).
* LAUNCH/OPS — ``prewarm()`` under a tp mesh compiles the whole family
  so subsequent serving compiles ZERO programs; ``swap_params`` accepts
  a full HOST param tree and re-shards it; chaos event counts at
  ``serving-admit``/``serving-step`` are tp-invariant (the host control
  loop is layout-blind); a 2-replica router over disjoint 2-chip tp
  groups survives a mid-wave replica kill token-identically.

The whole file runs on the 8-virtual-CPU-device platform tests/
conftest.py arms (``eight_devices`` skips otherwise).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
    tp_device_groups,
)
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    Router,
    ServingStats,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)

KW = dict(num_classes=16, dim=64, depth=2, heads=4, dtype=jnp.float32)

MAX_LEN = 32
# repetitive suffixes so the speculative cases' n-gram drafter gets hits
PROMPTS = [[1, 2, 3, 1, 2, 3, 1, 2], [4, 5, 4, 5, 4, 5], [6, 7, 8, 9],
           [2, 4, 2, 4, 2, 4]]


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, tp=1, **ekw):
    return InferenceEngine(
        model, params, slots=2, max_len=MAX_LEN, tp=tp,
        scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(16,),
                                max_queue=len(PROMPTS)),
        **ekw)


def _serve(model, params, tp=1, max_new=6, prompts=PROMPTS, **ekw):
    eng = _engine(model, params, tp=tp, **ekw)
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run()
    outs = [list(r.generated) for r in reqs]
    eng.close()
    return outs


@pytest.fixture(scope="module")
def native(eight_devices):
    return _model_and_params()


@pytest.fixture(scope="module")
def int8(eight_devices):
    return _model_and_params(kv_cache_dtype="int8")


@pytest.fixture(scope="module")
def refs(native, int8):
    """tp=1 greedy output per KV dtype — dense/paged/k/spec invariance at
    tp=1 is already pinned by test_serving/test_kv_paging/
    test_speculative, so one dense reference per dtype suffices."""
    return {
        "native": _serve(*native, tp=1),
        "int8": _serve(*int8, tp=1),
    }


# ----------------------------------------------------------------------
# parity: the composition matrix


# (tp, kv_dtype, paged, decode_ahead, speculative): the whole composition
# matrix at tp=2 — {dense, paged} x {native, int8 KV} x decode_ahead {1, 8}
# x {plain, speculative} — and a slice of it at tp=4
CASES = [(2, kvd, paged, k, spec)
         for paged in (False, True) for kvd in ("native", "int8")
         for k in (1, 8) for spec in (False, True)] + [
    (4, "native", False, 8, False),
    (4, "int8", True, 1, False),
    (4, "native", False, 1, True),
    (4, "native", True, 8, False),
]


@pytest.mark.parametrize(
    "tp,kvd,paged,k,spec", CASES,
    ids=[f"tp{t}-{d}-{'paged' if p else 'dense'}-k{k}-"
         f"{'spec' if s else 'plain'}" for t, d, p, k, s in CASES])
def test_tp_parity(native, int8, refs, tp, kvd, paged, k, spec):
    model, params = native if kvd == "native" else int8
    ekw = {"decode_ahead": k}
    if paged:
        ekw["kv_page_size"] = 8
    if spec:
        ekw.update(speculative="ngram", draft_len=3)
    assert _serve(model, params, tp=tp, **ekw) == refs[kvd]


# ----------------------------------------------------------------------
# memory: per-chip bytes 1/tp in both layouts, stats plumbing


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_per_chip_bytes_drop_by_tp(native, paged):
    model, params = native
    ekw = {"kv_page_size": 8} if paged else {}
    sizes = {}
    for tp in (1, 2, 4):
        eng = _engine(model, params, tp=tp, **ekw)
        sizes[tp] = (eng.weight_bytes_per_chip(), eng.kv_bytes_per_chip())
        s = eng.stats.summary()
        assert s["tp"] == tp
        assert s["kv_bytes_per_chip"] == sizes[tp][1]
        assert s["weight_bytes_per_chip"] == sizes[tp][0]
        eng.close()
    for tp in (2, 4):
        w_ratio = sizes[1][0] / sizes[tp][0]
        kv_ratio = sizes[1][1] / sizes[tp][1]
        # embeddings/logits replicate (weights) and the paged block
        # table/index replicate (KV) — the honest tax inside ±10%
        assert 0.9 * tp <= w_ratio <= 1.1 * tp, (tp, w_ratio)
        assert 0.9 * tp <= kv_ratio <= 1.1 * tp, (tp, kv_ratio)


def test_stats_memory_merges_into_rollup(eight_devices):
    """merge: homogeneous tp survives, per-chip = max, cluster = sum of
    per_chip * tp; unstamped engines -> None (never NaN); mixed tp ->
    tp None.  Strict JSON end to end."""
    import json

    a, b = ServingStats(2), ServingStats(2)
    a.memory(tp=2, kv_bytes_per_chip=100, weight_bytes_per_chip=1000)
    b.memory(tp=2, kv_bytes_per_chip=80, weight_bytes_per_chip=1000)
    m = ServingStats.merge([a, b])
    assert m["tp"] == 2
    assert m["kv_bytes_per_chip"] == 100          # worst chip anywhere
    assert m["kv_bytes_cluster"] == (100 + 80) * 2
    assert m["weight_bytes_cluster"] == 2000 * 2
    json.dumps(m)  # strict JSON (raises on NaN/inf by default upcast)

    c = ServingStats(2)  # never stamped
    m2 = ServingStats.merge([c])
    assert m2["kv_bytes_per_chip"] is None
    assert m2["kv_bytes_cluster"] is None
    b.memory(tp=4, kv_bytes_per_chip=80, weight_bytes_per_chip=1000)
    assert ServingStats.merge([a, b])["tp"] is None  # heterogeneous


# ----------------------------------------------------------------------
# launch/ops under the mesh


def test_prewarm_under_tp_then_zero_serving_compiles(native):
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        CompileTracker,
    )

    model, params = native
    tracker = CompileTracker.install()
    eng = _engine(model, params, tp=2)
    eng.prewarm()
    before = tracker.snapshot()
    reqs = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    d = CompileTracker.delta(tracker.snapshot(), before)
    assert d["n_compiled_programs"] == 0, d["by_site"]
    assert all(r.status == "done" for r in reqs)
    eng.close()


def test_flash_prefill_under_tp_is_a_head_sharded_island(eight_devices):
    """A model whose prefill attention is the Pallas flash kernel serves
    under tp through a shard_map island over the head axis (GSPMD cannot
    partition a Mosaic call — the four-chip v5e refused it; interpreted
    here, the island must still give the tp=1 tokens, GQA included)."""
    model, params = _model_and_params(attn="flash", heads_kv=2)
    want = _serve(model, params)
    assert _serve(model, params, tp=2) == want
    eng = _engine(model, params, tp=2)
    assert eng._prefill is not None and eng.model.attn == "flash"
    hlo = jax.jit(lambda p, t, n: eng._prefill(p, t, n)[1]).lower(
        eng.params, jnp.zeros((1, 16), jnp.int32),
        jnp.asarray([4], jnp.int32)).as_text()
    assert "shard_map" in hlo or "manual" in hlo.lower()
    eng.close()


def test_swap_params_reshards_host_tree_under_tp(native, refs):
    """swap_params at tp=2 with a full HOST (numpy) tree from a different
    seed: the engine re-shards it wholesale and serves the new weights'
    tokens (pinned against a tp=1 engine built on those weights)."""
    model, params = native
    model2, params2 = _model_and_params(seed=3)
    want2 = _serve(model2, params2, tp=1)

    eng = _engine(model, params, tp=2)
    reqs = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    assert [list(r.generated) for r in reqs] == refs["native"]
    host_tree = jax.tree.map(np.asarray, jax.device_get(params2))
    eng.swap_params(host_tree)
    leaf = jax.tree.leaves(eng.params)[0]
    assert "tp" in str(leaf.sharding)  # re-sharded, not host-resident
    reqs2 = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    assert [list(r.generated) for r in reqs2] == want2
    eng.close()


def test_chaos_event_counts_tp_invariant(native):
    """The chaos clock (one serving-admit per admission attempt, one
    serving-step per window dispatch) ticks in the HOST control loop —
    sharding the device programs must not move a single event."""
    model, params = native
    counts = {}
    for tp in (1, 2, 4):
        inj = FaultInjector(FaultPlan(faults=()))
        eng = _engine(model, params, tp=tp, chaos=inj)
        for p in PROMPTS:
            eng.submit(p, max_new=6)
        eng.run()
        eng.close()
        counts[tp] = (inj.events("serving-admit"),
                      inj.events("serving-step"))
    assert counts[1] == counts[2] == counts[4], counts
    assert counts[1][0] >= len(PROMPTS) and counts[1][1] > 0


def test_router_failover_over_disjoint_tp_groups(native, refs):
    """2 replicas x disjoint 2-chip groups (two-parameter factory:
    make_engine(tid, replica_index) -> tp_devices=groups[index]); chaos
    kills replica decode mid-wave; the wave finishes token-identical
    with exactly one failover."""
    model, params = native
    groups = tp_device_groups(2, 2)
    assert len(groups) == 2 and not set(groups[0]) & set(groups[1])
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-step", kind="transient", at=(1,)),)))

    def make_engine(tid, index):
        return InferenceEngine(
            model, params, slots=2, max_len=MAX_LEN, tp=2,
            tp_devices=groups[index],
            scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(16,),
                                    max_queue=len(PROMPTS)),
            trace_tid=tid, chaos=inj, stall_timeout_s=None)

    with Router(make_engine, 2) as r:
        rrs = [r.submit(p, max_new=6) for p in PROMPTS]
        r.run_until_done()
        assert [list(rr.generated) for rr in rrs] == refs["native"]
        assert all(rr.status == "done" for rr in rrs)
        assert r.failovers == 1
        summ = r.summary()
        assert summ["tp"] == 2
        assert summ["kv_bytes_cluster"] is not None


# ----------------------------------------------------------------------
# quant x tp (ISSUE 12 satellite): int8 weights shard like their f32
# ancestors, scales ride the Megatron split, tokens never move


@pytest.fixture(scope="module")
def quant_ref(native):
    return _serve(*native, tp=1, quant="int8")


@pytest.mark.parametrize("tp", [2, 4])
def test_quant_tp_parity_and_scale_layout(native, quant_ref, tp):
    """quant engine at tp=N: token-identical to the tp=1 quant engine,
    int8 kernels column/row-sharded, column scales P('tp') and row
    scales replicated (per-output scale is uniform over the contraction
    axis, so it distributes over the psum)."""
    model, params = native
    eng = _engine(model, params, tp=tp, quant="int8")
    blk = eng.params["block_0"]
    assert blk["qkv"]["kernel"].dtype == jnp.int8
    assert "tp" in str(blk["qkv"]["scale"].sharding.spec)     # column
    assert "tp" not in str(blk["proj"]["scale"].sharding.spec)  # row
    reqs = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    assert [list(r.generated) for r in reqs] == quant_ref
    eng.close()


def test_quant_per_chip_weight_bytes(native):
    """Per-chip weight bytes: ~4x smaller than f32 at tp=1 (kernels go
    4 -> 1 byte; embed/norms/biases stay f32), and still ~1/tp under
    the mesh — the int8 tree sharded like any other."""
    model, params = native
    sizes = {}
    for tp in (1, 2, 4):
        eng = _engine(model, params, tp=tp, quant="int8")
        sizes[tp] = eng.weight_bytes_per_chip()
        assert eng.stats.summary()["quant"] == "int8"
        eng.close()
    feng = _engine(model, params, tp=1)
    full = feng.weight_bytes_per_chip()
    feng.close()
    assert 3.2 <= full / sizes[1] <= 4.0, (full, sizes[1])
    for tp in (2, 4):
        ratio = sizes[1] / sizes[tp]
        # replicated embed/logits-scale tax is proportionally LARGER on
        # the int8 tree, so the floor is looser than the f32 case
        assert 0.45 * tp <= ratio <= 1.1 * tp, (tp, ratio)


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_quant_swap_requantizes_and_reshards(native, tp):
    """swap_params with a full-precision HOST tree at each tp: the
    engine re-quantizes AND re-shards at the seam, pinned against a
    fresh tp=1 quant engine on those weights."""
    model, params = native
    model2, params2 = _model_and_params(seed=3)
    want2 = _serve(model2, params2, tp=1, quant="int8")

    eng = _engine(model, params, tp=tp, quant="int8")
    host_tree = jax.tree.map(np.asarray, jax.device_get(params2))
    eng.swap_params(host_tree)
    assert eng.params["block_0"]["qkv"]["kernel"].dtype == jnp.int8
    if tp > 1:
        assert "tp" in str(
            eng.params["block_0"]["qkv"]["kernel"].sharding)
    reqs = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    assert [list(r.generated) for r in reqs] == want2
    eng.close()


def test_chaos_event_counts_quant_invariant(native):
    """quant changes the device programs' dtypes, never the host
    control loop: admit/step event counts match the full-precision
    engine exactly at tp 1 and 2."""
    model, params = native
    counts = {}
    for quant in (None, "int8"):
        for tp in (1, 2):
            inj = FaultInjector(FaultPlan(faults=()))
            eng = _engine(model, params, tp=tp, quant=quant, chaos=inj)
            for p in PROMPTS:
                eng.submit(p, max_new=6)
            eng.run()
            eng.close()
            counts[(quant, tp)] = (inj.events("serving-admit"),
                                   inj.events("serving-step"))
    assert counts[(None, 1)] == counts[("int8", 1)] == counts[("int8", 2)]
    assert counts[(None, 1)][0] >= len(PROMPTS)


def test_router_failover_quant_token_identical(native, quant_ref):
    """2 quant replicas over disjoint 2-chip tp groups; chaos kills one
    mid-wave; the wave finishes on the quant reference tokens with
    exactly one failover, and the rollup reports quant."""
    model, params = native
    groups = tp_device_groups(2, 2)
    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-step", kind="transient", at=(1,)),)))

    def make_engine(tid, index):
        return InferenceEngine(
            model, params, slots=2, max_len=MAX_LEN, tp=2,
            tp_devices=groups[index], quant="int8",
            scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(16,),
                                    max_queue=len(PROMPTS)),
            trace_tid=tid, chaos=inj, stall_timeout_s=None)

    with Router(make_engine, 2) as r:
        rrs = [r.submit(p, max_new=6) for p in PROMPTS]
        r.run_until_done()
        assert [list(rr.generated) for rr in rrs] == quant_ref
        assert r.failovers == 1
        summ = r.summary()
        assert summ["quant"] == "int8"
        assert summ["tp"] == 2


def test_tp_must_divide_heads_whole(native):
    model, params = native
    with pytest.raises(ValueError, match="divide"):
        _engine(model, params, tp=3)
    gmodel, gparams = _model_and_params(heads_kv=2)
    with pytest.raises(ValueError, match="divide"):
        _engine(gmodel, gparams, tp=4)  # 4 does not divide heads_kv=2
