"""Context-parallel serving (ISSUE 20): the sequence axis sharded away.

The decisive properties:

* MESH — ``serving_mesh(tp, cp=)`` carves a 2-D cp×tp mesh (cp=1 stays
  the 1-axis tp mesh, bit-compatible with every existing engine), and
  ``tp_device_groups(n, tp, cp=)`` hands out DISJOINT cp·tp-chip groups,
  refusing non-divisible carves with a sized error.
* PARITY — ring-attention prefill + sequence-sharded paged KV at
  cp ∈ {2, 4} (and cp=2 × tp=2) is token-identical to cp=1, across
  int8 KV and speculative decoding — GSPMD moves the bytes, never the
  argmax.
* MEMORY — per-chip KV bytes land at ~1/cp of the cp=1 figure at a
  FIXED pool size; ``ServingStats.memory(cp=)`` rides ``merge`` into
  the rollup (homogeneous cp survives, heterogeneous → None, strict
  JSON).
* LAUNCH/OPS — ``prewarm()`` under a cp mesh compiles the whole
  cp-qualified family (``prefill[b16,cp2]``) so serving compiles ZERO
  programs; chaos event counts are cp-invariant.
* REFUSALS — dense layout, indivisible max_len/kv_pages, and
  attn_fn-bearing models refuse cp>1 with actionable errors.

The whole file runs on the 8-virtual-CPU-device platform tests/
conftest.py arms (``eight_devices`` skips otherwise).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
    serving_mesh,
    tp_device_groups,
)
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    ServingStats,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
)

KW = dict(num_classes=16, dim=64, depth=2, heads=4, dtype=jnp.float32)

MAX_LEN = 32
PROMPTS = [[1, 2, 3, 1, 2, 3, 1, 2], [4, 5, 4, 5, 4, 5], [6, 7, 8, 9],
           [2, 4, 2, 4, 2, 4]]


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, cp=1, **ekw):
    ekw.setdefault("kv_page_size", 8)
    return InferenceEngine(
        model, params, slots=2, max_len=MAX_LEN, cp=cp,
        scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(16,),
                                max_queue=len(PROMPTS)),
        **ekw)


def _serve(model, params, cp=1, max_new=6, prompts=PROMPTS, **ekw):
    eng = _engine(model, params, cp=cp, **ekw)
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
    return {
        "native": _serve(*native, cp=1),
        "int8": _serve(*int8, cp=1),
    }


# ----------------------------------------------------------------------
# mesh carving: the 2-D cp×tp grid and its group factory


@pytest.mark.parametrize("cp,tp", [(1, 2), (2, 1), (2, 2)])
def test_serving_mesh_cp_by_tp_shape(eight_devices, cp, tp):
    mesh = serving_mesh(tp, cp=cp)
    if cp == 1:
        # cp=1 is bit-compatible with the pre-ISSUE-20 1-axis mesh
        assert mesh.axis_names == ("tp",)
        assert mesh.devices.shape == (tp,)
    else:
        assert mesh.axis_names == ("cp", "tp")
        assert mesh.devices.shape == (cp, tp)
    # every device distinct, row-major carve from the default order
    flat = list(mesh.devices.flat)
    assert len(set(flat)) == cp * tp
    assert flat == list(jax.devices()[: cp * tp])


def test_tp_device_groups_cp_disjoint(eight_devices):
    groups = tp_device_groups(2, 2, cp=2)  # 2 groups × (cp2 × tp2) = 8
    assert len(groups) == 2
    assert all(len(g) == 4 for g in groups)
    assert not set(groups[0]) & set(groups[1])
    # each group carves its own cp×tp mesh
    mesh = serving_mesh(2, groups[1], cp=2)
    assert mesh.devices.shape == (2, 2)
    assert set(mesh.devices.flat) == set(groups[1])


def test_tp_device_groups_cp_rejects_non_divisible(eight_devices):
    with pytest.raises(ValueError, match=r"groups x cp x tp"):
        tp_device_groups(3, 2, cp=2)  # 12 > 8 devices
    with pytest.raises(ValueError, match=r"groups x cp x tp"):
        tp_device_groups(2, 2, cp=4)  # 16 > 8
    with pytest.raises(ValueError, match="cp"):
        tp_device_groups(2, 2, cp=0)
    with pytest.raises(ValueError):
        serving_mesh(2, cp=8)  # 16 > 8 devices, error names cp


# ----------------------------------------------------------------------
# parity: curated composition slice, every case vs its cp=1 reference


CASES = [
    # (cp, tp, kv_dtype, speculative)
    (2, 1, "native", False),
    (2, 1, "int8", False),
    (2, 1, "native", True),
    (4, 1, "native", False),
    (2, 2, "native", False),
]


@pytest.mark.parametrize(
    "cp,tp,kvd,spec", CASES,
    ids=[f"cp{c}-tp{t}-{d}-{'spec' if s else 'plain'}"
         for c, t, d, s in CASES])
def test_cp_parity(native, int8, refs, cp, tp, kvd, spec):
    model, params = native if kvd == "native" else int8
    ekw = {"tp": tp} if tp > 1 else {}
    if spec:
        ekw.update(speculative="ngram", draft_len=3)
    assert _serve(model, params, cp=cp, **ekw) == refs[kvd]


# ----------------------------------------------------------------------
# memory: per-chip KV bytes 1/cp at a fixed pool size, stats plumbing


def test_per_chip_kv_bytes_drop_by_cp(native):
    model, params = native
    sizes = {}
    for cp in (1, 2, 4):
        # FIXED pool size divisible by every cp: the ratio measures the
        # sequence sharding, not default kv_pages rounding
        eng = _engine(model, params, cp=cp, kv_pages=16)
        sizes[cp] = eng.kv_bytes_per_chip()
        s = eng.stats.summary()
        assert s["cp"] == cp
        assert s["kv_bytes_per_chip"] == sizes[cp]
        eng.close()
    for cp in (2, 4):
        ratio = sizes[1] / sizes[cp]
        # the replicated block table/index is the honest tax inside ±10%
        assert 0.9 * cp <= ratio <= 1.1 * cp, (cp, ratio)


def test_prompt_over_one_chips_kv_budget_serves_to_parity_at_cp2():
    """The max_len-ceiling story.  One chip's KV budget is set at 60% of
    the cp=1 engine's footprint: cp=1 does NOT fit it, cp=2 does.  A
    40-token prompt that needs that footprint is admitted at cp=2,
    prefills through the ring and decodes to the cp=1 engine's tokens
    exactly, greedy AND seeded-sampled (on virtual devices the cp=1
    engine physically fits, which is what makes it the reference)."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import SamplingParams

    model, params = _model_and_params(num_classes=64, dim=256, depth=4,
                                      heads=8)
    prompt = [(i * 7) % 62 + 1 for i in range(40)]
    sampled = SamplingParams(temperature=0.7, top_k=8, seed=123)
    got, kv = {}, {}
    for cp in (1, 2):
        eng = InferenceEngine(
            model, params, slots=2, max_len=64, cp=cp, kv_page_size=8,
            kv_pages=16,
            scheduler=FIFOScheduler(max_len=64, buckets=(48,), max_queue=8))
        reqs = [eng.submit(prompt, max_new=8, sampling=sp)
                for sp in (None, sampled)]
        eng.run()
        got[cp] = [list(r.generated) for r in reqs]
        kv[cp] = eng.kv_bytes_per_chip()
        eng.close()
    budget = int(kv[1] * 0.6)
    assert kv[1] > budget >= kv[2]
    assert all(len(t) == 8 for t in got[1]) and got[2] == got[1]


def test_stats_cp_merges_into_rollup():
    import json

    a, b = ServingStats(2), ServingStats(2)
    a.memory(tp=1, kv_bytes_per_chip=100, weight_bytes_per_chip=1000, cp=2)
    b.memory(tp=1, kv_bytes_per_chip=80, weight_bytes_per_chip=1000, cp=2)
    m = ServingStats.merge([a, b])
    assert m["cp"] == 2
    # cluster bytes multiply by the FULL chip count, tp * cp
    assert m["kv_bytes_cluster"] == (100 + 80) * 2
    json.dumps(m, allow_nan=False)
    b.memory(tp=1, kv_bytes_per_chip=80, weight_bytes_per_chip=1000, cp=4)
    assert ServingStats.merge([a, b])["cp"] is None  # heterogeneous
    # unstamped engines default cp=1, still strict-JSON
    assert ServingStats.merge([ServingStats(2)])["cp"] == 1


# ----------------------------------------------------------------------
# launch/ops under the cp mesh


def test_prewarm_under_cp_then_zero_serving_compiles(native):
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        CompileTracker,
    )

    model, params = native
    tracker = CompileTracker.install()
    eng = _engine(model, params, cp=2)
    warm = eng.prewarm()
    # the family is cp-qualified: one program per (site, shape, cp)
    assert any(s.startswith("prefill[") and s.endswith(",cp2]")
               for s in warm["by_site"]), warm["by_site"]
    # prefill + insert + extend + pick + window + reset + host glue: ~14
    # cold; the headroom makes a new tiny program a nudge, not a page
    assert warm["programs"] <= 26, warm["by_site"]
    before = tracker.snapshot()
    reqs = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    d = CompileTracker.delta(tracker.snapshot(), before)
    assert d["n_compiled_programs"] == 0, d["by_site"]
    assert all(r.status == "done" for r in reqs)
    eng.close()


def test_chaos_event_counts_cp_invariant(native):
    """The chaos clock ticks in the HOST control loop — sharding the
    sequence axis must not move a single event."""
    model, params = native
    counts = {}
    for cp in (1, 2, 4):
        inj = FaultInjector(FaultPlan(faults=()))
        eng = _engine(model, params, cp=cp, chaos=inj)
        for p in PROMPTS:
            eng.submit(p, max_new=6)
        eng.run()
        eng.close()
        counts[cp] = (inj.events("serving-admit"),
                      inj.events("serving-step"))
    assert counts[1] == counts[2] == counts[4], counts
    assert counts[1][0] >= len(PROMPTS) and counts[1][1] > 0


def test_kv_handoff_event_counts_cp_invariant(native):
    """... through a REAL prefill -> decode tier too: the same admit, step
    and kv-handoff events at cp 1 and 2, the same tokens, all done."""
    from distributed_tensorflow_ibm_mnist_tpu.serving import Router

    model, params = native
    roles = ["prefill", "decode"]
    counts, toks = {}, {}
    for cp in (1, 2):
        inj = FaultInjector(FaultPlan(faults=()))

        def make_engine(tid, index):
            return _engine(model, params, cp=cp, trace_tid=tid,
                           role=roles[index], chaos=inj)

        with Router(make_engine, 2, roles=roles, chaos=inj) as r:
            rrs = [r.submit(p, max_new=6) for p in PROMPTS]
            r.run_until_done(max_steps=500)
            assert all(rr.status == "done" for rr in rrs)
            toks[cp] = [list(rr.generated) for rr in rrs]
        counts[cp] = {site: inj.events(site) for site in
                      ("serving-admit", "serving-step", "kv-handoff")}
    assert counts[1] == counts[2] and toks[1] == toks[2], counts
    assert counts[1]["kv-handoff"] >= len(PROMPTS)


# ----------------------------------------------------------------------
# refusals: every cp>1 precondition with an actionable error


def test_cp_validation_refusals(native):
    model, params = native

    def build(**kw):
        return InferenceEngine(
            model, params, slots=2, max_len=MAX_LEN,
            scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(16,)), **kw)

    with pytest.raises(ValueError, match="cp"):
        build(cp=0)
    with pytest.raises(ValueError, match="kv_page_size"):
        build(cp=2)  # dense layout cannot shard the sequence axis
    with pytest.raises(ValueError, match="max_len"):
        build(cp=3, kv_page_size=8)  # 32 % 3 != 0
    with pytest.raises(ValueError, match="kv_pages"):
        build(cp=2, kv_page_size=8, kv_pages=9)  # explicit, indivisible
    # ring prefill owns the attn_fn seat — a model already carrying one
    # refuses cp>1 instead of silently dropping its kernel
    from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
        vanilla_attention,
    )

    model_fn = model.clone(attn_fn=vanilla_attention)
    with pytest.raises(ValueError, match="attn_fn"):
        InferenceEngine(
            model_fn, params, slots=2, max_len=MAX_LEN, cp=2,
            kv_page_size=8,
            scheduler=FIFOScheduler(max_len=MAX_LEN, buckets=(16,)))
