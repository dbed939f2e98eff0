"""Per-request sampling (ISSUE 13): params, seeded purity, distribution.

The decisive properties:

* SEEDED PURITY — a request's token stream is a pure function of its
  ``(prompt, SamplingParams)``: identical across ``decode_ahead``
  {1, 4, 8}, dense vs paged layouts, an engine restart, and a replay on
  a speculative engine at fixed config.  Position-keyed PRNG
  (``fold_in(base_key, n)`` for the token at generated index ``n``) is
  what buys this — the host's windowing never touches the key schedule.
* GREEDY LIMIT — ``temperature == 0`` requests are token-identical to
  the engine's greedy output across layouts × decode_ahead ×
  ±speculative: sampling rows ride the SAME program, selected by data.
* ONE PROGRAM FAMILY — after prewarm, serving any mix of per-request
  ``(temperature, top_p, top_k, seed)`` configs compiles ZERO new
  programs (top-k rides a per-slot int32 data plane — ISSUE 14).
* DISTRIBUTION — the speculative verify's rejection sampling (accept a
  draft with prob ``p_target(d)``, resample the masked residual on
  reject) emits the target sampling distribution exactly; chi-squared
  gated over >= 10k draws on a small vocab, for both a high-probability
  and an adversarial (least-likely) draft.
* EXACTLY-ONCE — a chaos-killed replica's sampled requests replay
  token-identical on a survivor with exactly-once streaming delivery.
* STATS — sampled-request accounting (counts, mean temperature, NLL
  histogram) flows through ``ServingStats`` and the router rollup.
* WORK FOLLOWS THE PLANES (ISSUE 29) — the pick sorts the vocabulary once,
  and only in a step where some decoding row samples with top-k or top-p
  on; an all-greedy step is argmax and the logprob.  Same bits as the
  two-sort composition, same one program, and the host counts the
  windows by the device's own predicate (``pick_work``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.core.generate import (
    _filter_minp_rows,
    _filter_sorted_rows,
    _pick_rows,
    _sample_window_core,
    _tempered_rows,
    _verify_sample_core,
    init_cache,
    make_decode_step,
    make_prefill,
    pick_work,
)
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    Router,
    SamplingParams,
    ServingStats,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.sampling import (
    base_key,
    first_pick,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [9, 1, 7]]

# chi-squared 99.9th-percentile critical values by dof (no scipy in the
# image; a fixed table keeps the gate dependency-free)
CHI2_999 = {1: 10.83, 2: 13.82, 3: 16.27, 4: 18.47, 5: 20.52,
            6: 22.46, 7: 24.32}


def _model_and_params(seed=0, **over):
    model = get_model("causal_lm", **{**KW, **over})
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("buckets", (8,))
    return InferenceEngine(model, params, **kw)


def _serve(model, params, sampling=None, prompts=PROMPTS, max_new=8, **kw):
    """Serve the wave; returns (token lists, logprob lists).  ``sampling``
    is one SamplingParams for every request or a per-request list."""
    eng = _engine(model, params, **kw)
    if not isinstance(sampling, (list, tuple)):
        sampling = [sampling] * len(prompts)
    reqs = [eng.submit(np.asarray(p, np.int32), max_new=max_new, sampling=s)
            for p, s in zip(prompts, sampling)]
    eng.run()
    eng.close()
    assert all(r.status == "done" for r in reqs)
    return ([list(r.generated) for r in reqs],
            [list(r.logprobs) for r in reqs])


# ----------------------------------------------------------------------
# SamplingParams: validation at submit, key derivation


def test_sampling_params_validation_and_key():
    assert not SamplingParams().sampled              # greedy default
    assert SamplingParams(temperature=0.7).sampled
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=float("nan"))
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=1.5)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=-0.2)
    # top_p filters a sampling distribution: meaningless at temperature 0
    with pytest.raises(ValueError, match="temperature > 0"):
        SamplingParams(temperature=0.0, top_p=0.9)
    with pytest.raises(ValueError, match="seed"):
        SamplingParams(temperature=1.0, seed=-1)
    with pytest.raises(ValueError, match="seed"):
        SamplingParams(temperature=1.0, seed=1 << 64)
    with pytest.raises(ValueError, match="seed"):
        SamplingParams(temperature=1.0, seed=True)

    # the base key IS jax.random.PRNGKey(seed)'s raw data — host-derived
    # (no device dispatch at submit) — for 32-bit seeds; past 32 bits the
    # host derivation keeps the high word PRNGKey silently truncates
    # under the default x64-disabled config, so distinct seeds stay
    # distinct keys across the whole documented [0, 2^64) range
    for s in (0, 5, (1 << 31) + 9):
        np.testing.assert_array_equal(
            base_key(s), np.asarray(jax.random.key_data(
                jax.random.PRNGKey(s)), np.uint32).reshape(-1)[-2:])
    for s in ((1 << 32) + 7, (1 << 63) + 3):
        np.testing.assert_array_equal(
            base_key(s),
            np.asarray([(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], np.uint32))
    np.testing.assert_array_equal(
        SamplingParams(temperature=1.0, seed=5).key(), base_key(5))


def test_top_k_validation():
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(temperature=1.0, top_k=-1)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(temperature=1.0, top_k=True)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(temperature=1.0, top_k=2.5)
    # top_k filters a sampling distribution: meaningless at temperature 0
    with pytest.raises(ValueError, match="temperature > 0"):
        SamplingParams(temperature=0.0, top_k=3)
    assert SamplingParams(temperature=1.0, top_k=5).top_k == 5


def test_filter_topk_rows_per_row_support():
    """The data-plane top-k filter (ISSUE 14): each ROW keeps its own k
    highest logits and floors the rest; k=0 and k>=vocab are per-row
    no-ops (the off states), all in one (B, V) program."""
    rng = np.random.default_rng(0)
    raw = rng.normal(size=(4, 16)).astype(np.float32)  # no ties w.h.p.
    ks = jnp.asarray([0, 1, 3, 16], jnp.int32)
    out = np.asarray(_filter_sorted_rows(
        jnp.asarray(raw), ks, jnp.zeros((4,), jnp.float32)))  # top-p off
    neg = np.finfo(np.float32).min
    np.testing.assert_array_equal(out[0], raw[0])      # 0 = filter off
    np.testing.assert_array_equal(out[3], raw[3])      # k >= vocab = off
    for row, k in ((1, 1), (2, 3)):
        keep = np.zeros(16, bool)
        keep[np.argsort(raw[row])[-k:]] = True
        np.testing.assert_array_equal(out[row][keep], raw[row][keep])
        assert (out[row][~keep] == neg).all(), (row, k)


def test_top_k_one_is_argmax_and_vocab_k_is_noop():
    """``top_k=1`` at ANY temperature is argmax — token-identical to the
    greedy engine (seed inert in effect); ``top_k >= vocab`` leaves the
    distribution untouched — stream-identical to the same seed without
    the filter.  Both ride the same compiled window as every other row."""
    model, params = _model_and_params(seed=8)
    want, _ = _serve(model, params)                   # greedy reference
    got, _ = _serve(model, params,
                    sampling=SamplingParams(temperature=1.7, top_k=1,
                                            seed=99))
    assert got == want
    v = KW["num_classes"]
    base, _ = _serve(model, params,
                     sampling=SamplingParams(temperature=0.9, seed=5))
    full, _ = _serve(model, params,
                     sampling=SamplingParams(temperature=0.9, top_k=v,
                                             seed=5))
    assert base == full


def test_min_p_validation():
    with pytest.raises(ValueError, match="min_p"):
        SamplingParams(temperature=1.0, min_p=-0.1)
    with pytest.raises(ValueError, match="min_p"):
        SamplingParams(temperature=1.0, min_p=1.5)
    with pytest.raises(ValueError, match="min_p"):
        SamplingParams(temperature=1.0, min_p=float("nan"))
    # min_p filters a sampling distribution: meaningless at temperature 0
    with pytest.raises(ValueError, match="temperature > 0"):
        SamplingParams(temperature=0.0, min_p=0.5)
    assert SamplingParams(temperature=1.0, min_p=0.25).min_p == 0.25


def test_filter_minp_rows_per_row_support():
    """The data-plane min-p filter (ISSUE 16 satellite): each ROW cuts
    tokens whose probability is below its own ``min_p * max_prob`` —
    the threshold scales with the row's confidence; min_p=0 is a per-row
    no-op and min_p=1 keeps only the argmax, all in one (B, V) program."""
    rng = np.random.default_rng(1)
    raw = rng.normal(size=(3, 16)).astype(np.float32)  # no ties w.h.p.
    mps = jnp.asarray([0.0, 0.5, 1.0], jnp.float32)
    out = np.asarray(_filter_minp_rows(jnp.asarray(raw), mps))
    neg = np.finfo(np.float32).min
    np.testing.assert_array_equal(out[0], raw[0])      # 0 = filter off
    probs = np.exp(raw[1]) / np.exp(raw[1]).sum()
    keep = probs >= 0.5 * probs.max()
    np.testing.assert_array_equal(out[1][keep], raw[1][keep])
    assert (out[1][~keep] == neg).all()
    top = np.argmax(raw[2])                            # 1 = argmax only
    assert out[2][top] == raw[2][top]
    mask = np.ones(16, bool)
    mask[top] = False
    assert (out[2][mask] == neg).all()


def test_min_p_one_is_argmax_and_zero_is_noop():
    """``min_p=1.0`` at ANY temperature keeps only the argmax — token-
    identical to the greedy engine (seed inert in effect); ``min_p=0``
    leaves the distribution untouched — stream-identical to the same
    seed without the filter.  Same compiled window as every other row."""
    model, params = _model_and_params(seed=9)
    want, _ = _serve(model, params)                   # greedy reference
    got, _ = _serve(model, params,
                    sampling=SamplingParams(temperature=1.5, min_p=1.0,
                                            seed=77))
    assert got == want
    base, _ = _serve(model, params,
                     sampling=SamplingParams(temperature=0.9, seed=5))
    off, _ = _serve(model, params,
                    sampling=SamplingParams(temperature=0.9, min_p=0.0,
                                            seed=5))
    assert base == off


def test_scheduler_submit_rejects_non_params():
    sched = FIFOScheduler(max_len=32, buckets=(8,))
    with pytest.raises(ValueError, match="SamplingParams"):
        sched.submit([1, 2], max_new=4, sampling=(0.7, 0.9))
    # a validated instance passes through onto the Request
    req = sched.submit([1, 2], max_new=4,
                       sampling=SamplingParams(temperature=0.7, seed=3))
    assert req.sampling.seed == 3 and req.logprobs == []


# ----------------------------------------------------------------------
# greedy limit: temperature == 0 rows == the greedy engine, everywhere


def test_greedy_limit_matches_engine_greedy_everywhere():
    model, params = _model_and_params(seed=1)
    want, _ = _serve(model, params)                  # engine-default greedy
    zero = SamplingParams(temperature=0.0, seed=123)  # seed must be inert
    for kw in ({}, {"decode_ahead": 4}, {"kv_page_size": 8},
               {"speculative": "ngram", "draft_len": 3},
               {"speculative": "ngram", "draft_len": 3, "decode_ahead": 4}):
        got, logps = _serve(model, params, sampling=zero, **kw)
        assert got == want, kw
        assert all(len(lp) == len(t) for lp, t in zip(logps, got))


def test_logprobs_are_raw_logits_log_softmax():
    """Every generated token carries log_softmax(RAW logits)[token] — the
    model's pre-temperature distribution.  Pinned against a reference
    prefill for the first token, greedy and sampled alike."""
    model, params = _model_and_params(seed=2)
    prompt = np.asarray([9, 4, 2], np.int32)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :3] = prompt
    _, last = make_prefill(model, 48)(
        params, jnp.asarray(padded), jnp.asarray([3], np.int32))
    ref = np.asarray(jax.nn.log_softmax(last, axis=-1))[0]

    for sp in (None, SamplingParams(temperature=1.1, top_p=0.9, seed=7)):
        toks, logps = _serve(model, params, sampling=sp,
                             prompts=[prompt], max_new=4)
        assert len(logps[0]) == len(toks[0]) == 4
        assert logps[0][0] == pytest.approx(float(ref[toks[0][0]]), abs=1e-5)
        assert all(lp <= 1e-6 for lp in logps[0])   # log-probs, not probs


# ----------------------------------------------------------------------
# seeded purity: the stream is a function of the seed, not the batching


def test_seeded_stream_invariant_across_k_layout_restart():
    model, params = _model_and_params(seed=3)
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=1234)
    want, want_lp = _serve(model, params, sampling=sp)  # decode_ahead=1
    for kw in ({"decode_ahead": 4}, {"decode_ahead": 8},
               {"kv_page_size": 8}, {}):               # {} = restart
        got, lp = _serve(model, params, sampling=sp, **kw)
        assert got == want, kw
        for a, b in zip(lp, want_lp):
            np.testing.assert_allclose(a, b, atol=1e-5, err_msg=str(kw))
    # a different seed is a different stream (vocab 16, 8 tokens, 4 reqs:
    # a full collision would be astronomically unlucky)
    other, _ = _serve(model, params,
                      sampling=SamplingParams(temperature=0.8, top_p=0.9,
                                              seed=4321))
    assert other != want


def test_spec_sampled_replay_token_identical():
    """At fixed engine config a speculative sampled serve replays
    token-identically (same seeds -> same accepts -> same residuals).
    The spec and plain sample PATHS differ by design — only their
    distributions and the greedy limit coincide."""
    model, params = _model_and_params(seed=4)
    mix = [SamplingParams(temperature=0.9, seed=i) for i in range(3)]
    mix.append(None)                                  # greedy rider
    kw = dict(speculative="ngram", draft_len=3)
    a, a_lp = _serve(model, params, sampling=mix, **kw)
    b, b_lp = _serve(model, params, sampling=mix, **kw)
    assert a == b and a_lp == b_lp
    # the greedy rider matches the all-greedy reference in the same batch
    want, _ = _serve(model, params)
    assert a[3] == want[3]


# ----------------------------------------------------------------------
# one program family: sampling configs are data, never shapes


def test_zero_new_programs_across_sampling_configs():
    model, params = _model_and_params(seed=5)
    mixes = [None, SamplingParams(temperature=0.7, top_p=0.9, seed=1),
             SamplingParams(temperature=1.3, top_k=4, seed=9),
             SamplingParams(temperature=0.4, top_p=0.3, top_k=7, seed=42),
             SamplingParams(temperature=0.9, min_p=0.2, seed=17)]
    for kw in ({"decode_ahead": 4},
               {"speculative": "ngram", "draft_len": 3}):
        eng = _engine(model, params, **kw)
        eng.prewarm()
        before = eng._compile.snapshot()
        reqs = [eng.submit(np.asarray(p, np.int32), max_new=8, sampling=s)
                for p, s in zip(PROMPTS, mixes)]
        eng.run()
        d = CompileTracker.delta(eng._compile.snapshot(), before)
        assert d["n_compiled_programs"] == 0, (kw, d)
        assert all(r.status == "done" for r in reqs)
        eng.close()


# ----------------------------------------------------------------------
# distribution: rejection sampling == target sampling, chi-squared gated


def _chi2_gate(counts, p, label):
    """Pearson chi-squared at the 99.9th percentile, merging categories
    with expected count < 5 (the classical validity floor) into one bin."""
    n = counts.sum()
    # a token outside the target's support (nucleus-filtered out) must
    # never be emitted at all — that's a correctness bug, not noise
    assert counts[p == 0].sum() == 0, f"{label}: emitted zero-prob token"
    counts, p = counts[p > 0], p[p > 0]
    exp = n * p
    small = exp < 5.0
    if small.any():
        counts = np.concatenate([counts[~small], [counts[small].sum()]])
        exp = np.concatenate([exp[~small], [exp[small].sum()]])
    assert exp.min() >= 1.0, f"{label}: degenerate target distribution"
    chi2 = float((((counts - exp) ** 2) / exp).sum())
    dof = len(counts) - 1
    assert chi2 < CHI2_999[dof], (
        f"{label}: chi2 {chi2:.2f} >= {CHI2_999[dof]} (dof {dof}) over "
        f"{int(n)} draws — emitted distribution != target")


def test_verify_rejection_sampling_matches_target_distribution():
    """>= 10k draws through the speculative verify on a vocab-8 model:
    the first emitted token's empirical distribution must match the
    tempered/nucleus target — whether the draft is the mode (mostly
    accepted) or the least likely token (mostly rejected -> residual)."""
    model, params = _model_and_params(seed=6, num_classes=8)
    B, reps, max_len = 512, 20, 16
    prompt = np.tile(np.asarray([[3, 5, 1, 6]], np.int32), (B, 1))
    prefill = make_prefill(model, max_len)
    cache0, last = prefill(params, jnp.asarray(prompt))
    pend = jnp.argmax(last, -1).astype(jnp.int32)     # pending first token
    # reference logits at the position the verify's lane 0 samples
    _, logits0 = make_decode_step(model, max_len)(params, cache0, pend)
    verify = jax.jit(functools.partial(
        _verify_sample_core, model, max_len=max_len, pad_id=0))

    for temp, topp, pick, label in ((1.2, 0.0, "hi", "plain/mode-draft"),
                                    (0.9, 0.85, "lo", "nucleus/worst-draft")):
        temps = jnp.full((B,), temp, jnp.float32)
        topps = jnp.full((B,), topp, jnp.float32)
        p = np.asarray(jax.nn.softmax(
            _tempered_rows(logits0[:1], temps[:1], topps[:1],
                           jnp.zeros((1,), jnp.int32),
                           jnp.zeros((1,), jnp.float32))))[0]
        draft = int(np.argmax(p) if pick == "hi" else np.argmin(p))
        chunk = np.zeros((B, 2), np.int32)
        chunk[:, 0] = np.asarray(pend)
        chunk[:, 1] = draft
        counts = np.zeros(p.size)
        for rep in range(reps):
            seeds = range(rep * B, (rep + 1) * B)
            keys = jnp.asarray(np.stack([base_key(s) for s in seeds]))
            _, toks, logps, acc, _ = verify(
                params, cache0, jnp.asarray(chunk),
                jnp.ones((B,), jnp.int32), jnp.ones((B,), bool),
                temps, topps, jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.float32), keys,
                jnp.zeros((B,), jnp.int32))
            np.add.at(counts, np.asarray(toks)[:, 0], 1)
        assert counts.sum() == B * reps >= 10_000
        _chi2_gate(counts, p, label)


# ----------------------------------------------------------------------
# the pick's work follows its planes (ISSUE 29): one sort, under a cond


def _two_sort_reference(logits, temps, topps, topks, minps):
    """What ``_tempered_rows`` computed before ISSUE 29, written out here
    so that it cannot move with the code: top-k with a sort of its own,
    the nucleus with a second sort, then min-p."""
    neg = jnp.finfo(logits.dtype).min
    vocab = logits.shape[-1]
    scaled = logits / jnp.where(temps > 0.0, temps, 1.0)[:, None]
    # top-k
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.clip(topks, 1, vocab).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    on = (topks > 0) & (topks < vocab)
    scaled = jnp.where(on[:, None], jnp.where(scaled < kth, neg, scaled),
                       scaled)
    # nucleus
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
    keep = jnp.concatenate(
        [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < topps[:, None]],
        axis=-1)
    cutoff = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    nucleus = (topps > 0.0) & (topps < 1.0)
    scaled = jnp.where(nucleus[:, None],
                       jnp.where(scaled < cutoff, neg, scaled), scaled)
    # min-p
    probs = jax.nn.softmax(scaled, axis=-1)
    cut = minps[:, None] * jnp.max(probs, axis=-1, keepdims=True)
    return jnp.where((minps > 0.0)[:, None],
                     jnp.where(probs < cut, neg, scaled), scaled)


V = 32
# name -> rows of (temperature, top_p, top_k, min_p)
PLANE_MIXES = {
    "topk_only": [(0.7, 0.0, 5, 0.0), (1.3, 0.0, 2, 0.0), (1.0, 0.0, 0, 0.0)],
    "topp_only": [(0.7, 0.9, 0, 0.0), (1.3, 0.35, 0, 0.0), (1.0, 0.0, 0, 0.0)],
    "both": [(0.7, 0.9, 5, 0.0), (0.4, 0.3, 7, 0.2), (1.6, 0.5, 0, 0.0),
             (1.0, 0.0, 3, 0.1)],
    "ties_at_kth": [(1.0, 0.0, 3, 0.0), (1.0, 0.8, 3, 0.0),
                    (0.5, 0.6, 12, 0.0)],
    "k_ge_vocab": [(0.9, 0.0, V, 0.0), (0.9, 0.0, V + 9, 0.0),
                   (0.9, 0.7, V, 0.0)],
    "p_ge_one": [(0.9, 1.0, 0, 0.0), (0.9, 1.0, 4, 0.0)],
    "k_one": [(1.7, 0.0, 1, 0.0), (1.7, 0.9, 1, 0.0)],
    "greedy_row_beside": [(0.0, 0.0, 0, 0.0), (0.8, 0.9, 6, 0.0),
                          (0.0, 0.0, 0, 0.0), (1.2, 0.0, 0, 0.3)],
}


def _planes(rows):
    t, p, k, m = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(p, jnp.float32),
            jnp.asarray(k, jnp.int32), jnp.asarray(m, jnp.float32))


def _logits(n, seed, ties=False):
    raw = np.random.default_rng(seed).normal(size=(n, V)).astype(np.float32)
    if ties:
        # a quarter-step grid: every row has runs of equal logits, so the
        # k-th value is shared and the nucleus cutoff lands inside a run
        raw = np.round(raw * 2.0) / 2.0
    return jnp.asarray(3.0 * raw)


@pytest.mark.parametrize("mix", sorted(PLANE_MIXES))
def test_one_sort_filters_equal_two_sort_reference_bitwise(mix):
    rows = PLANE_MIXES[mix]
    temps, topps, topks, minps = _planes(rows)
    logits = _logits(len(rows), seed=len(mix), ties=(mix == "ties_at_kth"))
    want = np.asarray(
        _two_sort_reference(logits, temps, topps, topks, minps))
    for fn in (_tempered_rows, jax.jit(_tempered_rows)):
        got = np.asarray(fn(logits, temps, topps, topks, minps))
        np.testing.assert_array_equal(got, want)
    # the filter did something wherever it was on (the case is not vacuous)
    on = [(0 < k < V) or (0.0 < p < 1.0) for _, p, k, _ in rows]
    cut = (want == np.finfo(np.float32).min).any(axis=-1)
    assert all(c for c, o in zip(cut, on) if o)


@pytest.mark.parametrize("key_seed", [0, 1, (1 << 31) + 7])
def test_all_greedy_pick_is_argmax_and_raw_logprob(key_seed):
    """All-greedy planes: the token is argmax and the logprob the raw
    ``log_softmax`` at it, EXACTLY, whatever keys ride along."""
    logits = _logits(5, seed=3, ties=True)  # ties: argmax's first-index rule
    zf, zi = jnp.zeros((5,), jnp.float32), jnp.zeros((5,), jnp.int32)
    keys = jnp.asarray(np.random.default_rng(key_seed).integers(
        0, 1 << 32, size=(5, 2), dtype=np.uint32))
    tok, logp = jax.jit(_pick_rows)(logits, zf, zf, zi, zf, keys)
    want = np.argmax(np.asarray(logits), axis=-1)
    np.testing.assert_array_equal(np.asarray(tok), want)
    raw = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_array_equal(
        np.asarray(logp), raw[np.arange(5), want])
    # and the first-token program is the same pick
    tok1, logp1 = first_pick(logits, zf, zf, zi, zf, keys, zi)
    np.testing.assert_array_equal(np.asarray(tok1), want)
    np.testing.assert_array_equal(np.asarray(logp1), np.asarray(logp))


def _sorts(jaxpr, in_cond=False):
    """``(sorts under some cond, sorts under none)`` in a jaxpr, through
    every nested jaxpr (pjit bodies, scan bodies, cond branches)."""
    inside = outside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            inside, outside = inside + in_cond, outside + (not in_cond)
        under = in_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            a, b = _sorts(sub, under)
            inside, outside = inside + a, outside + b
    return inside, outside


def _window_jaxpr():
    model, params = _model_and_params()
    b, max_len = 3, 16
    cache = init_cache(model, params, b, max_len)
    zf, zi = jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32)
    return jax.make_jaxpr(functools.partial(
        _sample_window_core, model, window=2, max_len=max_len, ragged=True,
        pad_id=0))(params, cache, zi, jnp.ones((b,), bool), zf, zf, zi, zf,
                   jnp.zeros((b, 2), jnp.uint32), zi)


def _pick_jaxpr():
    zf, zi = jnp.zeros((3,), jnp.float32), jnp.zeros((3,), jnp.int32)
    return jax.make_jaxpr(_pick_rows)(
        jnp.zeros((3, V)), zf, zf, zi, zf, jnp.zeros((3, 2), jnp.uint32))


@pytest.mark.parametrize("program", [_pick_jaxpr, _window_jaxpr],
                         ids=["pick_rows", "sample_window"])
def test_pick_sorts_once_and_only_under_a_cond(program):
    """The regression ISSUE 29 removed cannot come back unseen: the
    vocabulary sort is ONE, and no step pays it unconditionally."""
    assert _sorts(program().jaxpr) == (1, 0)


@pytest.mark.parametrize("sp", [
    SamplingParams(temperature=0.8, top_p=0.9, top_k=6, seed=21),  # sorts
    SamplingParams(temperature=0.8, min_p=0.1, seed=21),           # does not
], ids=["sorted_filters", "temperature_minp_only"])
def test_mixed_batch_rows_do_not_feel_each_other(sp):
    """A greedy row's token and logprob do not depend on whether its
    neighbour samples (the step takes another branch of the same program),
    and the sampled row's stream is what it is alone."""
    model, params = _model_and_params(seed=10)
    mix = [None, sp, None, None, None]
    toks, lps = _serve(model, params, sampling=mix)
    g_toks, g_lps = _serve(model, params)               # all greedy
    for i, s in enumerate(mix):
        if s is None:
            assert toks[i] == g_toks[i] and lps[i] == g_lps[i], i
    alone, alone_lp = _serve(model, params, sampling=[sp],
                             prompts=[PROMPTS[1]])
    assert toks[1] == alone[0]
    np.testing.assert_allclose(lps[1], alone_lp[0], atol=1e-5)
    assert toks[1] != g_toks[1]                          # it did sample


@pytest.mark.parametrize("seed", range(4))
def test_pick_work_is_one_expression_on_host_and_device(seed):
    """The host's ``sampled_windows`` / ``sorted_windows`` count and the
    program's ``lax.cond`` predicates are ``pick_work`` on the same
    planes: numpy mirrors and device arrays answer alike."""
    rng = np.random.default_rng(seed)
    n = 6
    active = rng.random(n) < 0.5
    temps = np.where(rng.random(n) < 0.4, rng.random(n) + 0.1,
                     0.0).astype(np.float32)
    topps = rng.choice([0.0, 0.5, 1.0], n).astype(np.float32)
    topks = rng.choice([0, 3, V, V + 1], n).astype(np.int32)
    host = pick_work(active, temps, topps, topks, V)
    dev = jax.jit(pick_work, static_argnums=4)(
        jnp.asarray(active), jnp.asarray(temps), jnp.asarray(topps),
        jnp.asarray(topks), V)
    assert tuple(map(bool, host)) == tuple(map(bool, dev))
    sampled = active & (temps > 0)
    on = ((topks > 0) & (topks < V)) | ((topps > 0) & (topps < 1))
    assert tuple(map(bool, host)) == (sampled.any(), (sampled & on).any())


def test_retired_sampled_slot_stops_the_sampled_branch():
    """The stale plane: a sampled request retires, greedy ones go on in
    the other slots and its slot stays empty.  Its plane row still says
    ``temperature > 0``; the active mask is what turns the sampled branch
    off — on the host's count and in the device's predicate alike."""
    model, params = _model_and_params(seed=11)
    eng = _engine(model, params, slots=3)
    sp = SamplingParams(temperature=0.9, top_p=0.8, seed=4)
    short = eng.submit(np.asarray(PROMPTS[0], np.int32), max_new=3,
                       sampling=sp)
    longs = [eng.submit(np.asarray(p, np.int32), max_new=12)
             for p in PROMPTS[1:3]]
    while short.status != "done":
        eng.step()
    at_retirement = eng.stats.summary()
    # every token after the first (first_pick's) came from one window
    assert at_retirement["sampled_windows"] == len(short.generated) - 1 == 2
    assert at_retirement["sorted_windows"] == 2
    eng.step()                        # a window after the retirement
    assert eng.has_work and all(r.status != "done" for r in longs)
    assert (eng._slot_temp > 0).any()                  # the plane is stale
    assert eng._pick_work == (False, False)            # the host's reading
    temps, topps, topks, _, _ = eng._planes_dev
    dev = pick_work(eng._active_dev, temps, topps, topks,
                    KW["num_classes"])                 # the device's
    assert (bool(dev[0]), bool(dev[1])) == (False, False)
    assert bool((temps > 0).any())     # `any(temps > 0)` would still sort
    eng.run()
    s = eng.stats.summary()
    assert s["sampled_windows"] == s["sorted_windows"] == 2 < s["n_windows"]
    eng.close()


# ----------------------------------------------------------------------
# failover: seeded replay is token-identical with exactly-once streaming


def test_router_failover_replays_sampled_exactly_once():
    """Chaos kills one replica mid-wave; its sampled collateral re-decodes
    on a survivor.  Seeded purity makes the replay token-identical, and
    the delivered high-water mark suppresses the replayed prefix — each
    stream sees every token exactly once."""
    model, params = _model_and_params(seed=7)
    mix = [SamplingParams(temperature=0.9, top_p=0.9, seed=i * 7 + 1)
           for i in range(len(PROMPTS) - 1)] + [None]

    def factory(tid, chaos=None):
        return InferenceEngine(
            model, params, slots=2, max_len=16,
            scheduler=FIFOScheduler(max_len=16, buckets=(8,), max_queue=16),
            trace_tid=tid, chaos=chaos, stall_timeout_s=None)

    # fault-free reference: one engine, same sampling
    eng = factory(0)
    want = [eng.submit(np.asarray(p, np.int32), max_new=6, sampling=s)
            for p, s in zip(PROMPTS, mix)]
    eng.run()
    eng.close()
    want_toks = [list(r.generated) for r in want]

    inj = FaultInjector(FaultPlan(faults=(
        FaultSpec(site="serving-step", kind="transient", at=(1,)),)))
    streams: dict[int, list[int]] = {}
    r = Router(lambda tid: factory(tid, chaos=inj), 2)
    rrs = [r.submit(p, max_new=6, sampling=s,
                    callback=lambda rr, tok: streams.setdefault(
                        rr.id, []).append(int(tok)))
           for p, s in zip(PROMPTS, mix)]
    r.run_until_done()
    assert [list(rr.generated) for rr in rrs] == want_toks
    assert all(rr.status == "done" for rr in rrs)
    assert r.failovers == 1
    moved = [rr for rr in rrs if rr.redispatches]
    assert moved                                     # someone was displaced
    for rr in rrs:                                   # exactly-once delivery
        assert streams.get(rr.id, []) == list(rr.generated)
        assert len(rr.logprobs) == len(rr.generated)
    # the rollup carries the sampled-traffic accounting (attempts of the
    # displaced sampled requests count too — they are engine records)
    summ = r.summary()
    assert summ["n_sampled_requests"] >= len(PROMPTS) - 1
    assert summ["mean_temperature"] == pytest.approx(0.9, abs=1e-4)
    assert summ["logprob_tokens"] > 0 and summ["nll_p50"] is not None
    r.close()


# ----------------------------------------------------------------------
# stats: schema stays stable, ratios null-not-NaN


def test_stats_sampling_fields_and_merge():
    model, params = _model_and_params(seed=8)
    eng = _engine(model, params)
    sp = SamplingParams(temperature=0.6, seed=11)
    reqs = [eng.submit(np.asarray(p, np.int32), max_new=5, sampling=s)
            for p, s in zip(PROMPTS[:2], (sp, None))]
    eng.run()
    s = eng.stats.summary()
    assert s["n_sampled_requests"] == 1
    assert s["mean_temperature"] == pytest.approx(0.6, abs=1e-4)
    assert s["logprob_tokens"] == sum(len(r.generated) for r in reqs)
    assert s["nll_p50"] is not None and s["nll_p50"] >= 0
    # the windows whose pick ran the sampled branch: the sampled request's
    # 4 tokens after its first; temperature alone asks for no sort
    assert s["sampled_windows"] == 4 <= s["n_windows"]
    assert s["sorted_windows"] == 0
    v = eng.stats.vitals()
    assert (v["sampled_windows"], v["sorted_windows"]) == (4, 0)
    eng.close()

    # empty stats: every sampling figure is null, never NaN, and the
    # merged rollup re-derives means from summed counters
    empty = ServingStats(slots=1)
    es = empty.summary()
    assert es["n_sampled_requests"] == 0
    assert es["mean_temperature"] is None and es["nll_p50"] is None
    assert es["sampled_windows"] == es["sorted_windows"] == 0
    other = ServingStats(slots=1)
    other.window(0.0, 0.0, steps=1, waste=0, sampled=True, sorted_=True)
    other.window(0.0, 0.0, steps=1, waste=0, sampled=True)
    other.window(0.0, 0.0, steps=1, waste=0)
    rolled = ServingStats.merge([eng.stats, other, empty])
    assert rolled["n_windows"] == s["n_windows"] + 3
    assert rolled["sampled_windows"] == 4 + 2
    assert rolled["sorted_windows"] == 0 + 1
    merged = ServingStats.merge([eng.stats, empty])
    assert merged["n_sampled_requests"] == 1
    assert merged["mean_temperature"] == pytest.approx(0.6, abs=1e-4)
    assert merged["nll_p50"] is not None
