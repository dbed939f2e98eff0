"""Autoregressive generation for the causal LM family (KV-cache decode).

The reference repo was a trainer only (SURVEY.md §2.1 — no inference
surface), but a language-model family without a decode path is half a
framework: this module turns a trained :class:`~..models.causal_lm.CausalLM`
into a text generator the TPU way — the whole generation is ONE compiled
program (prefill + a ``lax.while_loop`` over decode steps), not a Python
loop of device round-trips, so the per-dispatch host latency that dominates
naive decode loops is paid once per call.

Production decode semantics (VERDICT.md r3 item 3):

* **Ragged prompts** — ``gen(params, prompt, prompt_lens=lens)`` takes a
  right-padded (B, P) batch with per-row real lengths.  Each row's first
  sampled token comes from the logits at ITS last real position, its cache
  cursor starts at its own length (models/transformer.py keeps a (B,)
  per-row cursor), new K/V land at per-row positions, and RoPE rotates at
  per-row absolute offsets — so a batched decode of mixed-length prompts
  is position-for-position identical to decoding each prompt alone.
  (One carve-out: MoE models route each forward's tokens jointly, so
  under capacity PRESSURE a batch's drop pattern can differ from a
  solo run's — with capacity ample enough to drop nothing, the identity
  holds for MoE too.  Ragged MoE prefill sharpens this: right-pad
  positions go through the router alongside real tokens, so pad
  garbage can CLAIM expert capacity and displace real tokens' slots —
  pads compete, not just other rows' real tokens.  Size
  ``moe_capacity_factor`` for the padded (B, P) token count when
  serving ragged MoE batches; the pads' outputs themselves are masked
  off by the causal prefix and never affect real positions directly.)
  Right-padding works because causal attention never looks forward: real
  tokens can't see the pads, and the pad K/V beyond a row's cursor are
  masked by the causal prefix mask until generation overwrites them.
* **Stop tokens** — ``eos_id`` arms per-row early exit: a row that emits
  ``eos_id`` (the EOS itself is kept) is frozen — subsequent slots are
  ``pad_id``, its cursor stops advancing — and the whole while-loop exits
  as soon as EVERY row has finished, so a batch that stops early pays for
  the steps it used, not ``max_new``.

Mechanics: TransformerBlock's decode mode (models/transformer.py
``_decode_attention``) keeps per-block K/V caches in a flax ``cache``
variable collection, appended via per-row ``dynamic_update_slice`` at the
running (B,) ``cache_index``; RoPE rotates each chunk at its absolute
position, which is why ``pos="rope"`` (the family default) is required — a
learned position table cannot address positions incrementally, let alone
beyond its trained length.

    gen = make_generator(model, max_len=256, max_new=64, eos_id=2)
    tokens = gen(params, prompt)                       # greedy
    tokens = gen(params, prompt, rng=key)              # sampled if temperature>0
    tokens = gen(params, prompt, prompt_lens=lens)     # ragged batch

Round 6 split the episode into STEPWISE primitives the continuous-batching
serving engine (serving/engine.py) composes on the host: ``make_prefill``
(cache + last-position logits, exposed between calls), ``make_decode_step``
(one batched token step against a caller-owned cache), and ``init_cache``
(a zeroed slot cache in the decode layout).  ``make_generator`` is
re-expressed on the same ``_prefill_core``/``_decode_step_core`` math, so
the fused offline episode and the serving path cannot drift apart
(greedy parity is pinned in tests/test_serving.py).

ISSUE 5 adds :func:`make_decode_window` on the same step core: ``window``
fused decode+pick steps per dispatch (one ``lax.scan``), emitting a
(B, window) token block — the decode-ahead primitive that lets the serving
engine pay one host sync per k tokens instead of per token.

ISSUE 9 adds :func:`make_verify_window`, the speculative-decoding sibling:
instead of k sequential fused steps, ONE k-position target forward over a
host-drafted chunk (last token + up to k−1 proposed continuations), with
per-row acceptance computed in-program — the longest prefix of drafts the
model's own argmax reproduces, plus its one free correction token.  The
KV cursor is rewound to the acceptance point inside the same program;
rejected positions hold garbage K/V that the NEXT window's k-token chunk
overwrites before anything can attend it (decode attention writes before
it gathers, and the causal mask never looks past a query's own position).
The PUBLIC ``make_verify_window`` verifies greedily: argmax-vs-draft
acceptance is exact for greedy decoding and would bias any sampled
distribution.

ISSUE 13 adds the SAMPLING-aware siblings the serving engine composes:
:func:`_pick_rows` (argmax / temperature / top-p / top-k — ISSUE 14 —
selected by per-row *data* planes, never by program shape; since
ISSUE 29 its work follows the planes too, through ``lax.cond`` on
:func:`pick_work`: no sampled row, no filters; no top-k / top-p row, no
sort),
:func:`_sample_window_core`
(the decode-ahead scan with per-row fold-in PRNG keys and a position
counter threaded through the carry, emitting per-token logprobs), and
:func:`_verify_sample_core` (speculative REJECTION sampling: accept
draft ``d`` with prob ``min(1, p_target(d)/q_draft(d))`` — ``p(d)`` for
the point-mass n-gram drafter — and resample the residual on reject,
which preserves the target distribution exactly; the ``temperature=0``
rows reduce bit-for-bit to the argmax match).  One program serves every
``(temperature, top_p, top_k, seed)`` mix, so distinct per-request
configs never recompile.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def _cache_from_sown(intermediates, lens, max_len: int,
                     kv_cache_dtype: str = "native"):
    """Assemble the decode-cache pytree from the K/V each block sowed
    during the forward prefill: pad (B, P, H_kv, D) to the max_len cache
    and set every block's (B,) write cursor to the per-row prompt length
    (pad K/V beyond a row's length stay in the cache but sit above its
    cursor, so the causal mask hides them until decode overwrites them).
    ``kv_cache_dtype="int8"`` quantizes the sown K/V into the int8+scales
    layout the quantized decode cache uses (models/transformer.py
    ``quantize_kv_int8``) — the prefill itself still ran full-precision."""
    cache = {}
    for name, sub in intermediates.items():
        if "kv_cache" not in sub:
            continue
        k, v = sub["kv_cache"][0]
        pad = ((0, 0), (0, max_len - k.shape[1]), (0, 0), (0, 0))
        entry = {
            "index": jnp.broadcast_to(lens, (k.shape[0],)).astype(jnp.int32),
        }
        if kv_cache_dtype == "int8":
            from distributed_tensorflow_ibm_mnist_tpu.models.transformer import (
                quantize_kv_int8,
            )

            k_q, k_s = quantize_kv_int8(k)
            v_q, v_s = quantize_kv_int8(v)
            entry["k"] = jnp.pad(k_q, pad)
            entry["v"] = jnp.pad(v_q, pad)
            entry["k_scale"] = jnp.pad(k_s, pad[:3])
            entry["v_scale"] = jnp.pad(v_s, pad[:3])
        else:
            entry["k"] = jnp.pad(k, pad)
            entry["v"] = jnp.pad(v, pad)
        cache[name] = entry
    if not cache:
        raise ValueError(
            "prefill sowed no K/V — the model must pass sow_kv through to "
            "its TransformerBlocks (CausalLM does)"
        )
    return cache


def _prefill_core(model, params, prompt, lens, max_len: int):
    """The prefill math shared by :func:`make_generator` (one fused program)
    and :func:`make_prefill` (standalone jit for the serving engine): run the
    right-padded (B, P) prompt through the NORMAL forward (flash-friendly —
    see the in-``_gen`` note) with each block sowing its rotated K/V,
    assemble the (B, max_len) decode cache with every cursor at its row's
    real length, and return the logits at each row's last real position."""
    logits, vars_ = model.apply(
        {"params": params}, prompt, mutable=["intermediates"],
    )
    cache = _cache_from_sown(
        vars_["intermediates"], lens, max_len,
        getattr(model, "kv_cache_dtype", "native"))
    last = jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1)[:, 0]  # (B, V)
    return cache, last


def _decode_step_core(model, params, cache, tok, max_len: int, ragged: bool):
    """One batched decode step shared by :func:`make_generator` and
    :func:`make_decode_step`: append each row's token at its cursor, attend
    its causal prefix, return (updated cache, (B, V) next-token logits)."""
    step_logits, vars_ = model.apply(
        {"params": params, "cache": cache}, tok[:, None],
        decode=True, max_len=max_len, ragged=ragged,
        mutable=["cache"],
    )
    return vars_["cache"], step_logits[:, 0]


def make_prefill(model, max_len: int) -> Callable:
    """Build a jitted ``prefill(params, prompt, prompt_lens=None) ->
    (cache, last_logits)`` — the stepwise HALF-program the serving engine
    (serving/engine.py) composes with :func:`make_decode_step`.

    Unlike :func:`make_generator` (which hides the cache inside one compiled
    episode), this EXPOSES the decode-cache pytree between calls: the caller
    owns it, can insert prefilled rows into a larger slot cache, and can run
    any number of decode steps against it.  ``prompt`` is (B, P) int tokens
    with P <= max_len; ``prompt_lens`` (B,) marks real lengths in a
    right-padded batch (None = full rows).  Returns the cache (every block's
    K/V padded to max_len, cursors at the per-row lengths) and the (B, V)
    logits at each row's last real position — pick from these for the first
    generated token.  Compiles once per (B, P) shape; bucket prompt lengths
    (serving/scheduler.py) to bound the shape set.

    Prefill always emits the DENSE row layout, even when the engine decodes
    paged (``page_size > 0``): the prompt runs through the ordinary forward
    (no cache involved), and the paged engine copies the pages under the
    row's cursor into its page pool on insert (serving/kv_pool.py
    ``make_paged_insert``: the padded row is a whole number of pages, and
    those above the cursor are never copied) — the prefill program is
    byte-identical between the two cache layouts, so switching layouts
    never recompiles the prefill family.
    """
    if getattr(model, "page_size", 0):
        model = model.clone(page_size=0)  # prefill is layout-agnostic
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    if getattr(model, "sow_kv", None) is False:
        model = model.clone(sow_kv=True)  # arm the flash-prefill capture

    @jax.jit
    def prefill(params, prompt, prompt_lens=None):
        b, p = prompt.shape
        if p > max_len:
            raise ValueError(
                f"prompt length {p} exceeds max_len ({max_len})")
        prompt = prompt.astype(jnp.int32)
        lens = (
            jnp.full((b,), p, jnp.int32) if prompt_lens is None
            else jnp.asarray(prompt_lens, jnp.int32)
        )
        return _prefill_core(model, params, prompt, lens, max_len)

    return prefill


def make_decode_step(model, max_len: int, ragged: bool = True) -> Callable:
    """Build a jitted ``step(params, cache, tok) -> (cache, logits)`` — one
    batched single-token decode across every cache row.

    ``tok`` is (B,) int32 (each row's previous token), ``cache`` the pytree
    from :func:`make_prefill` / :func:`init_cache`; the returned logits are
    (B, V) at the new positions.  ``ragged=True`` (the default — the serving
    engine multiplexes independent requests, so cursors always differ) keeps
    the per-row cursor machinery; ``ragged=False`` is the uniform
    scalar-cursor fast path for lockstep batches (models/transformer.py
    ``ragged``).  Rows whose cursor the caller doesn't care about (free
    engine slots) decode garbage into their OWN rows only — cache writes are
    per-row, so occupied slots are unaffected.
    """
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")

    @jax.jit
    def step(params, cache, tok):
        return _decode_step_core(
            model, params, cache, tok.astype(jnp.int32), max_len, ragged)

    return step


def _decode_window_core(model, params, cache, tok, active, rngs,
                        max_len: int, ragged: bool, pick, pad_id: int):
    """``window`` fused decode+pick steps as ONE ``lax.scan`` — the
    decode-ahead primitive shared by :func:`make_decode_window` and the
    serving engine's windowed hot loop.

    ``active`` is a (B,) bool mask FROZEN for the whole window: inactive
    rows still decode (the batch shape is fixed) but their picked tokens
    are replaced with ``pad_id`` before being fed back and emitted.
    Correctness leans on the same per-row isolation the engine's idle
    slots already use: a row's cache writes land only in its own row, so
    an inactive row's garbage never touches an active row's prefix.
    Returns ``(cache, (B, window) tokens, (B,) last)`` — ``last`` is the
    final carry token, handed back so the caller can feed the next window
    without slicing the block on the host (one extra dispatch saved)."""
    active = jnp.asarray(active, bool)
    pad = jnp.asarray(pad_id, jnp.int32)

    def body(carry, rng):
        cache, tok = carry
        cache, logits = _decode_step_core(model, params, cache, tok,
                                          max_len, ragged)
        nxt = jnp.where(active, pick(logits, rng), pad)
        return (cache, nxt), nxt

    (cache, last), toks = jax.lax.scan(body, (cache, tok.astype(jnp.int32)),
                                       rngs)
    return cache, toks.T, last


def make_decode_window(model, max_len: int, window: int, ragged: bool = True,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 0.0, pad_id: int = 0) -> Callable:
    """Build a jitted ``win(params, cache, tok, active=None, rngs=None) ->
    (cache, tokens, last)`` — ``window`` fused decode+pick steps per
    dispatch (decode-ahead), the k-step sibling of :func:`make_decode_step`.

    One call runs a ``lax.scan`` of ``window`` single-token steps and
    emits a (B, window) token block: the caller pays ONE dispatch and ONE
    host readback per k tokens instead of per token, which is the whole
    economics of decode-ahead serving (serving/engine.py ``decode_ahead``).
    ``active`` (B,) bool freezes which rows are live for the window —
    inactive rows emit ``pad_id``; ``rngs`` is (window, ...) PRNG keys,
    one per step (required when ``temperature > 0``, ignored for greedy).
    Greedy windows are token-identical to ``window`` sequential
    :func:`make_decode_step` calls (pinned in tests/test_decode_ahead.py);
    sampled windows consume keys in scan order, so parity holds only for
    the same key schedule.

    The window is CACHE-LAYOUT agnostic: pass a paged model clone
    (``page_size > 0``) and the paged cache pytree from
    ``serving.kv_pool.init_paged_cache`` and the same scan decodes through
    the page pool — the layout lives in the model + cache contents, not in
    this wrapper (paged greedy windows are token-identical to dense ones;
    pinned in tests/test_kv_paging.py).
    """
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if temperature == 0.0 and (top_k or top_p):
        raise ValueError(
            "top_k/top_p filter a SAMPLING distribution; set temperature > 0")

    def pick(logits, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(rng, logits).astype(jnp.int32)

    @jax.jit
    def win(params, cache, tok, active=None, rngs=None):
        b = tok.shape[0]
        if active is None:
            active = jnp.ones((b,), bool)
        if rngs is None:
            if temperature != 0.0:
                raise ValueError(
                    "temperature > 0 samples from the model — pass rngs= "
                    "((window, ...) keys, one per step)")
            rngs = jnp.zeros((window, 2), jnp.uint32)  # greedy: unused
        return _decode_window_core(model, params, cache, tok, active, rngs,
                                   max_len, ragged, pick, pad_id)

    return win


def _cache_cursor(tree):
    """The (B,) decode cursor from a cache pytree — the first ``"index"``
    leaf found by recursive walk.  Every block keeps its own copy but they
    advance in lockstep, so any one of them IS the cursor; dense, int8 and
    paged layouts all store it under this key (serving/kv_pool.py keeps the
    paged layout's key aligned for exactly this reason)."""
    if hasattr(tree, "items"):
        if "index" in tree:
            return tree["index"]
        for sub in tree.values():
            got = _cache_cursor(sub)
            if got is not None:
                return got
    return None


def _with_cursor(tree, index):
    """Rebuild a cache pytree with EVERY ``"index"`` leaf replaced by
    ``index`` — the verify window's cursor rewind.  Walks mappings only
    (array leaves pass through untouched) and preserves the mapping type,
    so dict and FrozenDict caches keep their pytree structure (a structure
    change would miss the engine's jit cache and recompile)."""
    if hasattr(tree, "items"):
        out = {k: (index if k == "index" else _with_cursor(v, index))
               for k, v in tree.items()}
        return out if isinstance(tree, dict) else type(tree)(out)
    return tree


def _verify_window_core(model, params, cache, chunk, draft_lens, active,
                        max_len: int, pad_id: int):
    """ONE target forward over a (B, k) proposed chunk — the speculative
    verify primitive (ISSUE 9), sibling of :func:`_decode_window_core`.

    ``chunk[:, 0]`` is each row's last emitted token (not yet in cache —
    the same pending-token contract the decode window uses) and
    ``chunk[:, 1:]`` up to k−1 host-drafted continuations; ``draft_lens``
    (B,) counts each row's real drafts (shorter rows right-pad, the mask
    hides the padding).  The apply appends all k positions at the cursor
    and returns per-position logits; ``preds[:, j]`` is the model's greedy
    token AFTER consuming ``chunk[:, :j+1]``.  Draft d_j is accepted iff
    every earlier draft matched and ``preds[:, j] == d_j`` — a cumprod of
    the match mask — so the emitted tokens are exactly
    ``preds[:, :acc+1]``: the accepted drafts (token-equal to the preds
    prefix by construction) plus the model's one free correction /
    continuation token.  This is what makes speculative greedy decoding
    EXACT: every emitted token is the model's own argmax given the
    verified prefix, indistinguishable from sequential decode.

    The apply ran the cursor to ``idx0 + k``; it is REWOUND in-program to
    ``idx0 + acc + 1`` (``idx0`` for inactive rows).  Positions past the
    acceptance point hold garbage K/V — safe because the NEXT window's
    k-token chunk starts at the rewound cursor and spans the whole garbage
    region, and decode attention (dense and paged alike) writes its chunk
    before it gathers, with the causal mask never admitting a position
    past the query's own — so garbage is overwritten before anything can
    attend it.
    """
    chunk = chunk.astype(jnp.int32)
    k = chunk.shape[1]
    active = jnp.asarray(active, bool)
    draft_lens = jnp.asarray(draft_lens, jnp.int32)
    pad = jnp.asarray(pad_id, jnp.int32)
    idx0 = _cache_cursor(cache)
    if idx0 is None:
        raise ValueError(
            "cache pytree has no 'index' cursor leaf — not a decode cache")
    idx0 = jnp.asarray(idx0, jnp.int32)
    logits, vars_ = model.apply(
        {"params": params, "cache": cache}, chunk,
        decode=True, max_len=max_len, ragged=True, mutable=["cache"],
    )
    cache = vars_["cache"]
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # (B, k)
    lanes = jnp.arange(k - 1, dtype=jnp.int32)[None, :]          # draft lanes
    match = (preds[:, :-1] == chunk[:, 1:]) & (lanes < draft_lens[:, None])
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    acc = jnp.where(active, acc, 0)                              # (B,)
    n_emit = jnp.where(active, acc + 1, 0)
    emit = active[:, None] & (
        jnp.arange(k, dtype=jnp.int32)[None, :] < n_emit[:, None])
    toks = jnp.where(emit, preds, pad)
    last = jnp.take_along_axis(
        toks, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    last = jnp.where(active, last, pad)
    new_idx = jnp.minimum(idx0 + n_emit, max_len).astype(jnp.int32)
    return _with_cursor(cache, new_idx), toks, acc, last


def make_verify_window(model, max_len: int, draft_len: int,
                       pad_id: int = 0) -> Callable:
    """Build a jitted ``verify(params, cache, chunk, draft_lens,
    active=None) -> (cache, tokens, accepted, last)`` — the speculative
    verify program (ISSUE 9), the one-forward sibling of
    :func:`make_decode_window`.

    ``k = draft_len + 1`` positions per dispatch, STATIC like the decode
    window's k: column 0 of ``chunk`` (B, k) is each row's pending last
    token, columns 1..draft_len the host-drafted proposals (rows with
    fewer real drafts right-pad; ``draft_lens`` (B,) masks the padding).
    Returns the updated cache (cursor at the acceptance point), the (B, k)
    emitted block — ``accepted[b] + 1`` real tokens per active row,
    ``pad_id`` elsewhere — the per-row accepted-draft count, and the (B,)
    last emitted token (the next chunk's column 0).

    GREEDY ONLY: acceptance compares the model's argmax to the draft,
    which is exact for greedy decoding and would bias any sampled
    distribution — the serving engine refuses ``speculative=`` with
    ``temperature > 0`` at construction.  Economics: one k-position
    forward replaces up to k sequential decode steps when drafts hit; a
    total miss still emits 1 token (a plain decode step with k−1 wasted
    lanes), so the parity gate — output token-identical to non-speculative
    greedy — holds at ANY accept rate (pinned in
    tests/test_speculative.py).  Cache-layout agnostic exactly like the
    decode window: the cursor rewind rewrites every block's ``"index"``
    leaf, present in dense, int8 and paged pytrees alike.
    """
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    k = draft_len + 1

    @jax.jit
    def verify(params, cache, chunk, draft_lens, active=None):
        b, kk = chunk.shape
        if kk != k:
            raise ValueError(
                f"chunk must be (B, draft_len+1={k}), got (B, {kk})")
        if active is None:
            active = jnp.ones((b,), bool)
        return _verify_window_core(model, params, cache, chunk, draft_lens,
                                   active, max_len, pad_id)

    return verify


def _filter_sorted_rows(logits, top_ks, top_ps):
    """Per-row top-k, then nucleus, with ``top_k`` / ``top_p`` as DATA —
    the plane-driven sibling of :func:`_filter_logits`'s static branches,
    same keep rules, from ONE descending sort of ``[B, vocab]``.

    Top-k: the k highest logits survive, ties at the k-th value included;
    ``top_ks`` is (B,) int32, rows with ``top_k <= 0`` or ``>= vocab``
    pass through.  Nucleus, over what top-k left: ranks whose PRECEDING
    mass is < p survive, so the argmax always does; ``top_ps`` is (B,)
    float32, rows with ``top_p <= 0`` or ``>= 1`` pass through.  The
    nucleus needs the descending sort of the top-k-FILTERED logits, and
    that is the sort already taken with every entry below the k-th value
    floored (they are its tail already) — so the result is bit for bit
    what two filters with a sort each give (tests/test_sampling.py holds
    it to that reference)."""
    neg = jnp.finfo(logits.dtype).min
    vocab = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k = jnp.clip(top_ks, 1, vocab).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    k_on = ((top_ks > 0) & (top_ks < vocab))[:, None]
    logits = jnp.where(k_on & (logits < kth), neg, logits)
    sorted_desc = jnp.where(k_on & (sorted_desc < kth), neg, sorted_desc)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = jnp.concatenate(
        [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_ps[:, None]],
        axis=-1)
    cutoff = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
    p_on = ((top_ps > 0.0) & (top_ps < 1.0))[:, None]
    return jnp.where(p_on & (logits < cutoff), neg, logits)


def _filter_minp_rows(logits, min_ps):
    """Per-row min-p filter with ``min_p`` as DATA: tokens whose
    probability is below ``min_p * max_prob`` are cut, so the threshold
    scales with the model's confidence (a peaked distribution prunes
    aggressively, a flat one keeps its tail).  ``min_ps`` is (B,)
    float32; rows with ``min_p <= 0`` pass through unfiltered, so greedy
    and min-p-free rows ride the same program.  The argmax always
    survives (its prob equals max_prob and ``min_p <= 1``), so
    ``min_p = 1.0`` reduces to greedy."""
    neg = jnp.finfo(logits.dtype).min
    probs = jax.nn.softmax(logits, axis=-1)
    cutoff = min_ps[:, None] * jnp.max(probs, axis=-1, keepdims=True)
    filtered = jnp.where(probs < cutoff, neg, logits)
    on = min_ps > 0.0
    return jnp.where(on[:, None], filtered, logits)


def pick_work(active, temps, topps, topks, vocab: int):
    """What a pick over these rows has to compute, from the planes
    themselves: ``(samples, sorts)`` — some DECODING row has
    ``temperature > 0``; some such row has top-k or top-p on.  ``active``
    masks the rows that decode, because a retired slot keeps its stale
    planes until the slot is reused.

    Operators and ``.any()`` only, so the SAME expression is the device's
    ``lax.cond`` predicate (jnp planes, inside the program) and the
    host's ``sampled_windows`` / ``sorted_windows`` count (the engine's
    numpy mirrors)."""
    sampled = active & (temps > 0.0)
    sort_on = ((topks > 0) & (topks < vocab)) | ((topps > 0.0) & (topps < 1.0))
    return sampled.any(), (sampled & sort_on).any()


def _tempered_rows(logits, temps, topps, topks, minps, active=None):
    """The per-row SAMPLING distribution as filtered logits: temperature
    scaling (before the filters, matching :func:`make_generator`'s static
    order), then the data-driven top-k, nucleus, and min-p filters (top-k
    first, like the static path; min-p last so its confidence-relative
    cut applies to the already-truncated support).

    The two sorted filters share one sort (:func:`_filter_sorted_rows`),
    and that sort runs only if some ``active`` row (default: all) with
    ``temps > 0`` has one of them on (:func:`pick_work`, a ``lax.cond`` on
    the planes): temperature-only and min-p-only batches pay a softmax
    and no sort.  A row that samples gets the same bits either way.  Rows
    with ``temps <= 0`` get a well-defined placeholder (divide by 1) —
    their output is overridden by argmax in :func:`_pick_rows`, the
    placeholder just keeps the math NaN-free."""
    topks = jnp.asarray(topks, jnp.int32)
    if active is None:
        active = jnp.ones(temps.shape, bool)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)[:, None]
    scaled = logits / safe_t
    _, sorts = pick_work(active, temps, topps, topks, logits.shape[-1])
    scaled = jax.lax.cond(
        sorts, lambda x: _filter_sorted_rows(x, topks, topps), lambda x: x,
        scaled)
    return _filter_minp_rows(scaled, jnp.asarray(minps, jnp.float32))


def _pick_rows(logits, temps, topps, topks, minps, keys, active=None):
    """Data-driven per-row pick: (B, V) logits + per-row ``temps`` /
    ``topps`` / ``topks`` / ``minps`` / already-fold-in'd ``keys`` (B, 2)
    uint32 planes -> ``((B,) int32 token, (B,) float32 logprob)``.  Rows
    with ``temps <= 0`` take argmax (greedy); every (temperature, top_p,
    top_k, min_p) mix shares one program, whose WORK follows the planes:
    the sampled pick (filters + the categorical draw over ``[B, vocab]``)
    sits under a ``lax.cond`` on :func:`pick_work` — "some ``active`` row
    (default: all) has ``temps > 0``" — so an all-greedy step computes
    argmax and the logprob and nothing else.  A greedy row's token does
    not depend on which branch its neighbours put the step on.

    The logprob is always ``log_softmax`` of the RAW logits at the
    emitted token — the model's own distribution, before temperature or
    nucleus reshaping — so best-of-n scores are comparable across
    sampling configs and greedy requests report calibrated confidences.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if active is None:
        active = jnp.ones(temps.shape, bool)
    samples, _ = pick_work(active, temps, topps, topks, logits.shape[-1])

    def sampled_pick():
        filtered = _tempered_rows(logits, temps, topps, topks, minps, active)
        sampled = jax.vmap(
            lambda l, k: jax.random.categorical(k, l))(filtered, keys)
        return jnp.where(temps > 0.0, sampled.astype(jnp.int32), greedy)

    tok = jax.lax.cond(samples, sampled_pick, lambda: greedy)
    logp = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), tok[:, None], axis=1)[:, 0]
    return tok, logp.astype(jnp.float32)


def _sample_window_core(model, params, cache, tok, active, temps, topps,
                        topks, minps, keys, pos, window: int, max_len: int,
                        ragged: bool, pad_id: int):
    """The sampling-aware decode-ahead window (ISSUE 13): ``window`` fused
    decode+pick steps as ONE ``lax.scan``, with the per-row sampling
    planes as runtime DATA and the PRNG threaded through the carry.

    ``temps``/``topps``/``minps`` are (B,) float32, ``topks`` (B,) int32,
    ``keys`` (B, 2) uint32 BASE keys
    (one per request, a pure function of its seed), ``pos`` (B,) int32 the
    per-row count of already-generated tokens.  The token at generated
    index ``n`` is picked with ``fold_in(base_key, n)``, and ``pos``
    advances in the carry for active rows only — so a request's token
    stream is a pure function of ``(seed, prefix)`` regardless of how the
    host batches it into windows: decode_ahead k, engine restarts, and
    router failover replays all land on the identical key schedule.
    Returns ``(cache, (B, window) tokens, (B, window) logprobs, (B,) last,
    (B,) new_pos)``; inactive rows emit ``pad_id`` / 0.0 logprob."""
    active = jnp.asarray(active, bool)
    pad = jnp.asarray(pad_id, jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    topps = jnp.asarray(topps, jnp.float32)
    topks = jnp.asarray(topks, jnp.int32)
    minps = jnp.asarray(minps, jnp.float32)
    keys = jnp.asarray(keys, jnp.uint32)
    step = active.astype(jnp.int32)

    def body(carry, _):
        cache, tok, pos = carry
        cache, logits = _decode_step_core(model, params, cache, tok,
                                          max_len, ragged)
        step_keys = jax.vmap(jax.random.fold_in)(keys, pos)
        nxt, logp = _pick_rows(logits, temps, topps, topks, minps,
                               step_keys, active)
        nxt = jnp.where(active, nxt, pad)
        logp = jnp.where(active, logp, 0.0)
        return (cache, nxt, pos + step), (nxt, logp)

    (cache, last, pos), (toks, logps) = jax.lax.scan(
        body, (cache, tok.astype(jnp.int32), jnp.asarray(pos, jnp.int32)),
        None, length=window)
    return cache, toks.T, logps.T, last, pos


def _verify_sample_core(model, params, cache, chunk, draft_lens, active,
                        temps, topps, topks, minps, keys, pos,
                        max_len: int, pad_id: int):
    """Speculative verify with REJECTION SAMPLING (ISSUE 13) — the
    sampling-aware sibling of :func:`_verify_window_core`, sharing its
    one-forward / cursor-rewind mechanics and its (B, k) chunk contract.

    Per draft lane ``j`` (draft ``d_j = chunk[:, j+1]``, target filtered
    distribution ``p_j`` from the row's temperature/top-p planes): accept
    with prob ``min(1, p_j(d_j) / q_j(d_j))`` — the n-gram drafter is a
    point mass, ``q_j(d_j) = 1``, so the accept prob is ``p_j(d_j)``
    against a uniform draw.  The first rejected lane emits a sample from
    the RESIDUAL ``max(p_j - q_j, 0)`` renormalized (= ``p_j`` with
    ``d_j`` masked out); a fully-accepted chunk emits the bonus token
    sampled plain from the last position.  This is the standard
    speculative-sampling identity: the emitted marginal equals sampling
    ``p_j`` directly, at any draft quality, so PR 9's speedup extends to
    sampled traffic without biasing the distribution (chi-squared gated
    in tests/test_sampling.py).

    PRNG discipline mirrors :func:`_sample_window_core`: the token at
    generated index ``n`` owns base-fold ``K_n = fold_in(base, n)`` —
    plain/bonus samples draw from ``K_n``, the accept uniform from
    ``fold_in(K_n, 1)``, the residual resample from ``fold_in(K_n, 2)``,
    so replays are token-identical and never reuse a draw.  Rows with
    ``temps <= 0`` reduce via ``where`` to the EXACT argmax match of the
    greedy core — same acceptances, same tokens, bit for bit.  Returns
    ``(cache, (B, k) tokens, (B, k) logprobs, (B,) accepted, (B,) last)``
    with logprobs from the raw-logits ``log_softmax`` like every pick.
    """
    chunk = chunk.astype(jnp.int32)
    b, k = chunk.shape
    dl = k - 1
    active = jnp.asarray(active, bool)
    draft_lens = jnp.asarray(draft_lens, jnp.int32)
    pad = jnp.asarray(pad_id, jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    topps = jnp.asarray(topps, jnp.float32)
    topks = jnp.asarray(topks, jnp.int32)
    minps = jnp.asarray(minps, jnp.float32)
    keys = jnp.asarray(keys, jnp.uint32)
    pos = jnp.asarray(pos, jnp.int32)
    idx0 = _cache_cursor(cache)
    if idx0 is None:
        raise ValueError(
            "cache pytree has no 'index' cursor leaf — not a decode cache")
    idx0 = jnp.asarray(idx0, jnp.int32)
    logits, vars_ = model.apply(
        {"params": params, "cache": cache}, chunk,
        decode=True, max_len=max_len, ragged=True, mutable=["cache"],
    )
    cache = vars_["cache"]
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # (B, k)

    # the per-position filtered target distribution, flattened to rows
    flat = logits.reshape(b * k, -1)
    filt = _tempered_rows(flat, jnp.repeat(temps, k),
                          jnp.repeat(topps, k),
                          jnp.repeat(topks, k),
                          jnp.repeat(minps, k),
                          jnp.repeat(active, k)).reshape(b, k, -1)
    probs = jax.nn.softmax(filt, axis=-1)                        # (B, k, V)

    # generated index per position and its key family (flattened B*k)
    posj = (pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :])
    pick_key = jax.vmap(jax.random.fold_in)(
        jnp.repeat(keys, k, axis=0), posj.reshape(-1))           # (B*k, 2)
    u_key = jax.vmap(lambda kk: jax.random.fold_in(kk, 1))(pick_key)
    res_key = jax.vmap(lambda kk: jax.random.fold_in(kk, 2))(pick_key)
    u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(
        u_key).reshape(b, k)

    # acceptance: sampled rows by rejection test, greedy rows by match
    d = chunk[:, 1:]                                             # (B, dl)
    p_draft = jnp.take_along_axis(
        probs[:, :-1, :], d[..., None], axis=-1)[..., 0]         # (B, dl)
    lanes = jnp.arange(dl, dtype=jnp.int32)[None, :]
    valid = lanes < draft_lens[:, None]
    accept = jnp.where(temps[:, None] > 0.0,
                       u[:, :dl] < p_draft,
                       preds[:, :-1] == d) & valid
    acc = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)
    acc = jnp.where(active, acc, 0)                              # (B,)

    # candidate token at EVERY position: residual resample where a draft
    # could have been rejected (lane < draft_lens), plain sample past the
    # drafts (the bonus / short-draft continuation) — only position
    # j == acc is ever emitted
    neg = jnp.finfo(filt.dtype).min
    vocab = filt.shape[-1]
    res_logits = jnp.where(
        jax.nn.one_hot(d, vocab, dtype=bool), neg, filt[:, :dl, :])
    cand_res = jax.vmap(lambda l, kk: jax.random.categorical(kk, l))(
        res_logits.reshape(b * dl, -1),
        res_key.reshape(b, k, 2)[:, :dl].reshape(b * dl, 2),
    ).reshape(b, dl).astype(jnp.int32)
    cand_plain = jax.vmap(lambda l, kk: jax.random.categorical(kk, l))(
        filt.reshape(b * k, -1), pick_key,
    ).reshape(b, k).astype(jnp.int32)
    jidx = jnp.arange(k, dtype=jnp.int32)[None, :]
    cand_res = jnp.concatenate(
        [cand_res, jnp.full((b, 1), pad, jnp.int32)], axis=1)
    cand = jnp.where(jidx < draft_lens[:, None], cand_res, cand_plain)
    cand = jnp.where(temps[:, None] > 0.0, cand, preds)

    drafts_pad = jnp.concatenate(
        [d, jnp.full((b, 1), pad, jnp.int32)], axis=1)           # (B, k)
    out = jnp.where(jidx < acc[:, None], drafts_pad,
                    jnp.where(jidx == acc[:, None], cand, pad))
    n_emit = jnp.where(active, acc + 1, 0)
    emit = active[:, None] & (jidx < n_emit[:, None])
    toks = jnp.where(emit, out, pad)
    logps = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), toks[..., None],
        axis=-1)[..., 0].astype(jnp.float32)
    logps = jnp.where(emit, logps, 0.0)
    last = jnp.take_along_axis(
        toks, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    last = jnp.where(active, last, pad)
    new_idx = jnp.minimum(idx0 + n_emit, max_len).astype(jnp.int32)
    return _with_cursor(cache, new_idx), toks, logps, acc, last


def init_cache(model, params, batch: int, max_len: int, shardings=None):
    """A zeroed (batch, max_len) decode-cache pytree in the model's decode
    layout (same structure/dtypes a real prefill produces) — the serving
    engine's slot cache before any request is admitted.  Built from
    ``jax.eval_shape`` of the decode apply, so no forward pass runs.

    ``shardings``: optional congruent NamedSharding tree (the tensor-
    parallel engine's head-axis KV layout).  When given, the zeros are
    materialized DIRECTLY under those shardings (a jit with
    ``out_shardings``), so a cache bigger than one chip's memory never
    transits a single device — the allocation path of serving models
    that only exist sharded.

    DENSE layout only: a paged model (``page_size > 0``) decodes through a
    shared page pool whose size is serving configuration, not a model
    attribute — build that with ``serving.kv_pool.init_paged_cache``."""
    if getattr(model, "page_size", 0):
        raise ValueError(
            "init_cache builds the dense (batch, max_len) slot cache; a "
            "paged model (page_size > 0) decodes through a page pool — "
            "build it with serving.kv_pool.init_paged_cache, which also "
            "sizes the pool (n_pages is engine config)")
    return _zeros_like_shapes(
        cache_shapes(model, params, batch, max_len), shardings)


def cache_shapes(model, params, batch: int, max_len: int):
    """ShapeDtypeStruct tree of the dense (batch, max_len) decode cache —
    the probe :func:`init_cache` allocates from, exposed so a caller that
    needs a CONGRUENT tree before allocation (the tensor-parallel engine
    building its head-axis sharding tree) can derive one without running
    a forward pass."""
    return jax.eval_shape(
        lambda p: model.apply(
            {"params": p}, jnp.zeros((batch, 1), jnp.int32),
            decode=True, max_len=max_len, ragged=True, mutable=["cache"],
        )[1]["cache"],
        params,
    )


def _zeros_like_shapes(shapes, shardings=None):
    """Zeros for an eval_shape tree — placed per ``shardings`` when given
    (each chip materializes only its own shard), default-device otherwise."""
    build = lambda: jax.tree.map(  # noqa: E731 - tiny local thunk
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    if shardings is None:
        return build()
    return jax.jit(build, out_shardings=shardings)()


def _filter_logits(logits, top_k: int, top_p: float):
    """Standard sampling filters on (B, V) logits, jit-friendly (static
    shapes, masking instead of truncation).

    ``top_k > 0`` keeps the k highest logits; ``0 < top_p < 1`` keeps the
    smallest set of tokens whose softmax mass reaches p (nucleus), always
    including the argmax.  Both compose (k first, then p).
    """
    neg = jnp.finfo(logits.dtype).min
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]  # (B, 1)
        logits = jnp.where(logits < kth, neg, logits)
    if 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep ranks whose PRECEDING mass is < p (so the argmax always
        # survives); the cutoff logit is the smallest kept one
        keep = jnp.concatenate(
            [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p], axis=-1)
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, neg, logits)
    return logits


def make_generator(
    model,
    max_len: int,
    max_new: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_id: int | None = None,
    pad_id: int = 0,
    with_lengths: bool = False,
    unroll: int = 1,
) -> Callable:
    """Build a jitted ``gen(params, prompt, rng=None, prompt_lens=None)
    -> (B, P+max_new)``.

    ``prompt`` is int tokens (B, P) with P + max_new <= max_len (the KV
    cache size, static); ``prompt_lens`` (B,) int32 marks each row's real
    length in a right-padded ragged batch (None = every row is full).
    Row b of the result is ``prompt[b, :len_b]``, then up to ``max_new``
    generated tokens, then ``pad_id`` — generation stops per row at
    ``eos_id`` (kept in the output) and the compiled loop exits early
    once every row has stopped.

    ``with_lengths=True`` returns ``(tokens, gen_lens)`` with ``gen_lens``
    (B,) int32 — the number of REAL generated tokens per row (EOS
    included; ``max_new`` for rows that never stopped).  This is the
    reliable way to recover per-row outputs when the vocabulary may
    legitimately emit ``pad_id`` as an ordinary token (r4 advisor: with
    EOS armed, a sampled pad is otherwise indistinguishable from
    post-EOS fill — row b's generation is
    ``tokens[b, len_b : len_b + gen_lens[b]]``).

    ``temperature == 0`` decodes greedily (argmax); otherwise
    logits/temperature are sampled categorically with ``rng``, optionally
    filtered by ``top_k`` (keep the k best) and/or ``top_p`` (nucleus:
    smallest set reaching p probability mass).  The returned callable is
    compiled once per (prompt length, batch) shape; reuse it across calls
    (Trainer.generate caches it for you).

    ``unroll`` replicates the decode-scan body and applies ONLY to the
    ``eos_id=None`` scan path (the EOS early-exit while_loop cannot
    unroll); measured a rejection on the v5e (see the in-body note) and
    kept at 1 there — the knob exists for other hardware.
    """
    if max_new < 1:
        raise ValueError(f"max_new must be >= 1, got {max_new}")
    if unroll < 1:
        raise ValueError(
            f"unroll must be >= 1, got {unroll} (it replicates the decode-"
            "scan body; note it applies only to the eos_id=None scan path)"
        )
    if temperature == 0.0 and (top_k or top_p):
        raise ValueError(
            "top_k/top_p filter a SAMPLING distribution; set temperature > 0"
        )
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    if eos_id is not None and eos_id == pad_id:
        raise ValueError(
            f"eos_id and pad_id must differ (both {eos_id}): a pad fed back "
            "after a stop would immediately re-trigger the stop logic"
        )
    if getattr(model, "sow_kv", None) is False:
        model = model.clone(sow_kv=True)  # arm the flash-prefill capture

    def pick(logits, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # temperature BEFORE the filters (the standard order): the nucleus
        # must be p mass of the distribution actually being sampled
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(rng, logits).astype(jnp.int32)

    def gen(params, prompt, rng=None, prompt_lens=None):
        # lengths are data to the compiled program, so value errors can't
        # raise in-trace — validate here, where callers pass concrete
        # arrays (a 0 or >P length would silently corrupt the cache
        # cursor); tracers (a gen nested in someone's jit) skip the check
        if prompt_lens is not None and not isinstance(prompt_lens, jax.core.Tracer):
            import numpy as np

            lens_c = np.asarray(prompt_lens)
            if lens_c.shape != (prompt.shape[0],):
                raise ValueError(
                    f"prompt_lens must be shape ({prompt.shape[0]},) — one "
                    f"length per row — got {lens_c.shape}"
                )
            if lens_c.min() < 1 or lens_c.max() > prompt.shape[1]:
                raise ValueError(
                    f"prompt_lens must be in [1, P={prompt.shape[1]}], got "
                    f"range [{lens_c.min()}, {lens_c.max()}]"
                )
        # compile accounting (utils/tracing): each (B, P) shape of the one-
        # shot episode compiles a fresh program — attribute it to a site
        # naming this generator's static config so program-family growth
        # from generator reuse-misses is visible in bench/trace output
        from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import compile_site

        with compile_site(f"generator[L{max_len},n{max_new}]"):
            return _gen(params, prompt, rng, prompt_lens)

    @functools.partial(jax.jit, static_argnames=())
    def _gen(params, prompt, rng=None, prompt_lens=None):
        b, p = prompt.shape
        if p + max_new > max_len:
            raise ValueError(
                f"prompt ({p}) + max_new ({max_new}) exceeds max_len ({max_len})"
            )
        if rng is None:
            if temperature != 0.0:
                raise ValueError(
                    "temperature > 0 samples from the model — pass rng= "
                    "(repeated calls would otherwise all return the "
                    "PRNGKey(0) sample)"
                )
            rng = jax.random.PRNGKey(0)  # greedy: rngs are split but unused
        prompt = prompt.astype(jnp.int32)
        lens = (
            jnp.full((b,), p, jnp.int32) if prompt_lens is None
            else jnp.asarray(prompt_lens, jnp.int32)
        )
        # FLASH PREFILL: run the prompt through the ordinary forward (the
        # model's own attention — the Pallas flash kernel for attn="flash")
        # with each block sowing its rotated K/V, then assemble the decode
        # cache from the sown tensors.  A decode-mode prefill would attend
        # every prompt position over the full max_len cache — O(P*max_len)
        # scores, OOM for long prompts; this path is O(P^2)-blockwise
        # through the kernel and never materializes more.  Right-padded
        # ragged rows ride through unchanged: causal attention keeps real
        # tokens from seeing the pads after them.  (_prefill_core is the
        # same math make_prefill jits standalone — the serving engine's
        # half-program; here it inlines into the one fused episode.)
        cache, last = _prefill_core(model, params, prompt, lens, max_len)
        # each row's first sample comes from ITS last real position
        rngs = jax.random.split(rng, max_new)
        first = pick(last, rngs[0])
        finished = (
            jnp.zeros((b,), bool) if eos_id is None else first == eos_id
        )
        toks = jnp.full((b, max_new), pad_id, jnp.int32).at[:, 0].set(first)

        # one decode step per iteration; early exit once every row stopped
        def cond(carry):
            _, _, finished, _, t, _ = carry
            live = t < max_new
            if eos_id is not None:
                live &= ~jnp.all(finished)
            return live

        # the per-row machinery is STATIC: uniform batches (prompt_lens
        # None) keep the scalar-cursor decode fast path — measured ~20%
        # of batched decode throughput at B=8 (models/transformer.py
        # ``ragged``, docs/PERFORMANCE.md).  Finished rows keep decoding
        # in lockstep (their cursors advance with everyone's, bounded by
        # the P+max_new<=max_len contract) and their sampled tokens are
        # overwritten with pad — freezing their cursors would make the
        # cursors per-row and force the slow path.
        ragged = prompt_lens is not None

        def step(cache, tok, finished, step_rng):
            # same batched step make_decode_step jits standalone for the
            # serving engine — inlined here into the fused episode
            cache, step_logits = _decode_step_core(
                model, params, cache, tok, max_len, ragged)
            nxt = pick(step_logits, step_rng)
            if eos_id is not None:
                nxt = jnp.where(finished, pad_id, nxt)
                finished = finished | (nxt == eos_id)
            return cache, nxt, finished

        if eos_id is None:
            # static trip count -> lax.scan (XLA pipelines it measurably
            # better than the equivalent while_loop: ~8% at B=32).
            # ``unroll`` replicates the step body — tried against the
            # kernel-launch-bound small-model decode (the roofline note in
            # docs/PERFORMANCE.md) and MEASURED a rejection on the v5e:
            # B=1 +3% at unroll=8, B=8 −23% at unroll>=4 (each step's
            # cache dynamic_update_slice chain serializes, so unrolling
            # only bloats the program).  Kept at 1; the knob remains for
            # other hardware.
            def sbody(carry, step_rng):
                cache, tok = carry
                cache, nxt, _ = step(cache, tok, finished, step_rng)
                return (cache, nxt), nxt

            (_, _), rest = jax.lax.scan(sbody, (cache, first), rngs[1:],
                                        unroll=unroll)
            toks = jnp.concatenate([first[:, None], rest.T], axis=1)
            flen = jnp.full((b,), max_new, jnp.int32)  # no stop: all real
        else:
            # EOS early exit needs a data-dependent loop: one decode step
            # per iteration, done as soon as EVERY row has stopped.
            # flen records each row's real generated length (EOS slot
            # included) the step it finishes — the per-row recovery
            # handle when pad_id is also a legitimate vocab token.
            def body(carry):
                cache, tok, finished, toks, t, flen = carry
                cache, nxt, fin2 = step(cache, tok, finished, rngs[t])
                toks = toks.at[:, t].set(nxt)
                flen = jnp.where(fin2 & ~finished, t + 1, flen)
                return (cache, nxt, fin2, toks, t + 1, flen)

            flen = jnp.where(finished, 1, max_new).astype(jnp.int32)
            carry = (cache, first, finished, toks,
                     jnp.asarray(1, jnp.int32), flen)
            _, _, _, toks, _, flen = jax.lax.while_loop(cond, body, carry)

        # assemble (B, P+max_new): each row's real prompt, its generated
        # tokens at ITS length, pad everywhere else
        keep = jnp.arange(p)[None, :] < lens[:, None]
        base = jnp.where(keep, prompt, pad_id)
        out = jnp.concatenate(
            [base, jnp.full((b, max_new), pad_id, jnp.int32)], axis=1)
        out = jax.vmap(
            lambda row, g, i: jax.lax.dynamic_update_slice(row, g, (i,))
        )(out, toks, lens)
        return (out, flen) if with_lengths else out

    gen._jitted = _gen  # the compiled core (tests assert its cache stays warm)
    return gen


def generate(model, params, prompt, max_new: int, max_len: int | None = None,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             rng=None, eos_id: int | None = None, pad_id: int = 0,
             prompt_lens=None, with_lengths: bool = False):
    """One-shot convenience over :func:`make_generator` (compiles per call —
    build the generator once for repeated use, or call Trainer.generate,
    which caches it)."""
    prompt = jnp.asarray(prompt)
    if prompt.ndim == 1:
        prompt = prompt[None, :]
    if max_len is None:
        max_len = int(prompt.shape[1]) + max_new
    return make_generator(model, max_len, max_new, temperature, top_k, top_p,
                          eos_id=eos_id, pad_id=pad_id,
                          with_lengths=with_lengths)(
        params, prompt, rng=rng, prompt_lens=prompt_lens
    )
