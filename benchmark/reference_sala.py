"""The plain reference of MiniCPM-SALA as this repo's ``SalaLM`` runs it:
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernels, no cache, no batching, one layer at a time over one sequence, in
blocks of rows so that 20k tokens fit beside a serving engine.

Source: https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json
(the catalog's row: ``config`` and ``described_as``).  What that does not
fix is set by the family's conventions and marked ``assumed`` here and in
``configs/minicpm-sala-serve-d12.json``.

Stack (``r = scale_depth / sqrt(32)``: the residual scale keeps the
PUBLISHED depth under the depth cut; ``mup_denominator`` is taken as
initialisation-only, assumed)::

    h0 = scale_emb * E[tok]
    h += r * Mixer_l(RMSNorm(h));  h += r * W_down(silu(W_gate u) * W_up u),
    u = RMSNorm(h);   logits = W_head(RMSNorm(h) / (hidden / dim_model_base))

RMSNorm epsilon ``rms_norm_eps``, a learned scale, no biases anywhere, head
untied.

``minicpm4`` layers: q = heads x head_dim, k, v = KV heads x head_dim;
RMSNorm over the head dimension on q and k (``qk_norm``, a learned scale
per projection, assumed shared by the heads); no rotary embedding
(``attn_use_rope`` false); scores / sqrt(head_dim); causal.  A query whose
context (its position + 1) is at most ``dense_len`` attends to all of it.
Beyond that (InfLLM-v2 as in MiniCPM4's published ``sparse_config``,
assumed: kernel_size 32, kernel_stride 16, block_size 64, init_blocks 1,
window_size 2048, topk 64, dense_len 8192):

    Kc_j = mean(k[16 j : 16 j + 32])                   compressed keys
    r_h  = softmax_j(q_h . Kc_j / sqrt(head_dim))      over the kernels whose
                                                       last token is at or
                                                       before the query
    R    = sum of r_h over the query heads of a KV group
    score(block b) = max of R over the kernels that overlap block b

and the query attends, causally, to the tokens of: block 0; the 32 blocks
that end with its own (``window_size / block_size`` whole blocks, the
family's block-aligned window, assumed); and the 64 best-scoring of the
blocks between — one selection per KV group per query.  The selection is
float32 throughout.  ``y = W_o(o * sigmoid(W_g x))`` (``attn_use_output_gate``).

``lightning-attn`` layers: q, k, v = 32 heads x 128 each; the same qk-norm;
rotary embedding (theta ``rope_theta``, pairs (d, d + D/2)) on q and k;

    S_t = lam_h S_{t-1} + k_t^T v_t      (128 x 128 per head, float32)
    o_t = (q_t / sqrt(128)) S_t
    lam_h = exp(-2^(-8 (h + 1) / 32))    (Lightning Attention's slopes, the
                                          same in every layer, assumed)

``y = W_o(RMSNorm_4096(concat_h o) * sigmoid(W_g x))`` (``use_output_norm``,
``use_output_gate``).  Here the recurrence runs token by token.

Departures from the published model: none known beyond the ``assumed``
entries above.  It reads the program's parameter tree by its names
(``embed``, ``block_<i>/{norm_attn, q_proj, k_proj, v_proj, g_proj, o_proj,
q_norm, k_norm, out_norm, norm_mlp, mlp_gate, mlp_up, mlp_down}``,
``norm_out``, ``logits``) and shares no code with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024    # rows of the sequence per projection / MLP call
QROWS = 128    # queries per attention call
HEADS = 8      # lightning heads scanned at once (what of q, k, v is held)


def shape_of(cfg: dict) -> dict:
    """The reference's static arguments from a configuration file."""
    sp = cfg["assumed_sizes"]["sparse_config"]
    return {
        "mixers": tuple(cfg["mixer_types"]), "heads": cfg["num_attention_heads"],
        "heads_kv": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "l_heads": cfg["lightning_nh"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]), "scale_emb": float(cfg["scale_emb"]),
        "residual": cfg["scale_depth"] / cfg["residual_layers"] ** 0.5,
        "logit_div": cfg["hidden_size"] / cfg["dim_model_base"],
        "sparse": tuple(sp[k] for k in (
            "kernel_size", "kernel_stride", "block_size", "init_blocks",
            "window_size", "topk", "dense_len")),
    }


def _w(p):
    return p["kernel"].astype(jnp.float32)


def rms_norm(x, p, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"].astype(jnp.float32)


def rope(x, theta):
    """(S, H, D): rotate pair (d, d + D/2) by pos * theta^(-2d/D)."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("eps", "names", "cols"))
def _project(p, x, *, eps, names, cols=None):
    """RMSNorm(x) through the layer's input projections (their output
    columns ``cols[0]:cols[1]`` when given), for a block of rows."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["norm_attn"], eps)
        lo, hi = cols or (0, None)
        return tuple(h @ _w(p[n])[:, lo:hi] for n in names)


@functools.partial(jax.jit, static_argnames=("eps", "residual"))
def _mix_out(p, x, o, *, eps, residual):
    """x + r * W_o(o * sigmoid(W_g RMSNorm(x))) for a block of rows."""
    with jax.default_matmul_precision("highest"):
        gate = rms_norm(x, p["norm_attn"], eps) @ _w(p["g_proj"])
        return x + residual * ((o * jax.nn.sigmoid(gate)) @ _w(p["o_proj"]))


@functools.partial(jax.jit, static_argnames=("eps", "residual"))
def _mlp(p, x, *, eps, residual):
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["norm_mlp"], eps)
        u = jax.nn.silu(u @ _w(p["mlp_gate"])) * (u @ _w(p["mlp_up"]))
        return x + residual * (u @ _w(p["mlp_down"]))


def _by_rows(fn, *xs):
    """``fn`` over blocks of ``ROWS`` rows of every array in ``xs``."""
    n = xs[0].shape[0]
    outs = [fn(*(x[a:a + ROWS] for x in xs)) for a in range(0, n, ROWS)]
    if not isinstance(outs[0], tuple):
        return jnp.concatenate(outs)
    cols = [list(c) for c in zip(*outs)]
    del outs  # one column's blocks at a time beside their concatenation
    return tuple(jnp.concatenate(cols.pop(0)) for _ in range(len(cols)))


@functools.partial(jax.jit, static_argnames=("sparse",))
def compressed_keys(k, *, sparse):
    """(S, Hkv, D) keys -> (J, Hkv, D): ``Kc_j = mean(k[stride j : stride j +
    kernel_size])`` for every kernel that lies wholly inside the sequence."""
    ksz, stride = sparse[:2]
    starts = jnp.arange((k.shape[0] - ksz) // stride + 1) * stride
    return jax.vmap(
        lambda a: jax.lax.dynamic_slice_in_dim(k, a, ksz).mean(0))(starts)


@functools.partial(jax.jit, static_argnames=("sparse", "low"))
def _sparse_rows(q, k, v, kc, first, *, sparse, low=()):
    """Attention of the ``QROWS`` queries at positions ``first ..`` over the
    whole sequence's keys (``kc`` their compressed keys), with each query's
    own selection.  Also returns the selected block ids (QROWS, Hkv, topk),
    ascending (all -1 for a query that attends densely)."""
    ksz, stride, bsz, init, window, topk, dense_len = sparse
    with jax.default_matmul_precision("highest"):
        s_len, hkv, d = k.shape
        n_q, nh, _ = q.shape
        g = nh // hkv
        qg = q.reshape(n_q, hkv, g, d)
        t = first + jnp.arange(n_q)                      # query positions
        pos = jnp.arange(s_len)
        n_blocks = -(-s_len // bsz)
        # ---- selection, from compressed keys
        n_kern = kc.shape[0]
        starts = jnp.arange(n_kern) * stride
        # the precision below: selection scores from bf16 operands
        qs, ks = (_bf16(qg), _bf16(kc)) if "scores" in low else (qg, kc)
        rel = jnp.einsum("qkgd,jkd->qkgj", qs, ks) / jnp.sqrt(jnp.float32(d))
        past = (starts + ksz - 1)[None, :] <= t[:, None]   # (Q, J)
        rel = jnp.where(past[:, None, None, :], rel, -jnp.inf)
        big = jnp.nan_to_num(jax.nn.softmax(rel, axis=-1)).sum(2)
        big = jnp.where(past[:, None, :], big, -1.0)       # (Q, Hkv, J)
        # kernel j overlaps block b iff their token ranges intersect: a few
        # consecutive kernels per block, found on the host from the sizes
        j0 = np.arange(n_kern)[:, None] * stride
        b0 = np.arange(n_blocks)[None, :] * bsz
        over = (j0 < b0 + bsz) & (j0 + ksz > b0)           # (J, B)
        span = int(over.sum(0).max())
        lo = np.minimum(over.argmax(0), n_kern - span)     # (B,)
        idx = lo[:, None] + np.arange(span)                # (B, span)
        hit = over[idx, np.arange(n_blocks)[:, None]]
        score = jnp.where(hit, big[..., idx], -1.0).max(-1)  # (Q, Hkv, B)
        blk = jnp.arange(n_blocks)
        own = (t // bsz)[:, None, None]
        local = window // bsz
        between = (blk >= init) & (blk <= own - local)            # (Q, 1, B)
        order = jnp.argsort(-jnp.where(between, score, -jnp.inf), axis=-1,
                            stable=True)[..., :topk]
        picked = jnp.sort(order, axis=-1)
        chosen = jnp.zeros(score.shape, bool).at[
            jnp.arange(n_q)[:, None, None], jnp.arange(hkv)[None, :, None],
            picked].set(True)
        chosen = chosen | (blk < init) | ((blk > own - local) & (blk <= own))
        is_sparse = (t + 1 > dense_len)[:, None, None]
        chosen = jnp.where(is_sparse, chosen, True)
        picked = jnp.where(is_sparse, picked, -1)
        # ---- attention over the chosen blocks' tokens, causal
        allow = chosen[..., pos // bsz] & (pos[None, None, :] <= t[:, None, None])
        sc = jnp.einsum("qkgd,nkd->qkgn", qg, k) / jnp.sqrt(jnp.float32(d))
        sc = jnp.where(allow[:, :, None, :], sc, -jnp.inf)
        o = jnp.einsum("qkgn,nkd->qkgd", jax.nn.softmax(sc, -1), v)
        return o.reshape(n_q, nh * d), picked


@functools.partial(jax.jit, static_argnames=("first", "total", "low"))
def _lightning_scan(q, k, v, upto, *, first, total, low=()):
    """The recurrence, token by token, for heads ``first ..`` of ``total``:
    (S, H, D) each -> (S, H * D), and the state (H, D, D) after the first
    ``upto`` tokens (it stops there: outputs from ``upto`` on are not the
    model's)."""
    with jax.default_matmul_precision("highest"):
        s_len, nh, d = q.shape
        h = jnp.arange(first + 1, first + nh + 1, dtype=jnp.float32)
        lam = jnp.exp(-(2.0 ** (-8.0 * h / total)))[:, None, None]

        def step(state, qkv):
            i, qt, kt, vt = qkv
            new = lam * state + kt[:, :, None] * vt[:, None, :]
            if "state" in low:  # the precision below: a bf16 state
                new = _bf16(new)
            state = jnp.where(i < upto, new, state)
            return state, jnp.einsum("hd,hde->he", qt / jnp.sqrt(jnp.float32(d)), state)

        state, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32),
                                (jnp.arange(s_len), q, k, v))
        return o.reshape(s_len, nh * d), state


LOW = ("weights", "scores", "state")  # what the precision below lowers


def _bf16(x):
    """float32 rounded to bfloat16's 8 bits of mantissa, kept as float32.
    ``reduce_precision`` and not a pair of ``astype``: the compiler removes
    a float32 -> bfloat16 -> float32 round trip (excess precision is allowed
    by default), and on the chip the lowered reference then IS the float32
    one (PR 28's first control read what the sound check read)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@jax.jit
def _fp8_weights(p):
    """Every matmul kernel of a layer rounded to float8 (4 bits of exponent,
    3 of mantissa, scaled per matrix into its range) and back: weights in
    the precision below bf16."""
    def lower(w):
        if w.ndim != 2:
            return w
        w32 = w.astype(jnp.float32)
        scale = 240.0 / jnp.max(jnp.abs(w32))
        return (jax.lax.reduce_precision(w32 * scale, exponent_bits=4,
                                         mantissa_bits=3) / scale).astype(w.dtype)
    return jax.tree.map(lower, p)


def _sparse_layer(p, x, sh, audit=None, low=()):
    nh, hkv, d = sh["heads"], sh["heads_kv"], sh["head_dim"]
    q, k, v = _by_rows(
        functools.partial(_project, p, eps=sh["eps"],
                          names=("q_proj", "k_proj", "v_proj")), x)
    s_len = x.shape[0]
    q = rms_norm(q.reshape(s_len, nh, d), p["q_norm"], sh["eps"])
    k = rms_norm(k.reshape(s_len, hkv, d), p["k_norm"], sh["eps"])
    v = v.reshape(s_len, hkv, d)
    kc = compressed_keys(k, sparse=sh["sparse"])
    outs, blocks = [], []
    for a in range(0, s_len, QROWS):
        o, picked = _sparse_rows(q[a:a + QROWS], k, v, kc, a,
                                 sparse=sh["sparse"], low=low)
        outs.append(o)
        if audit is not None:
            blocks.append(np.asarray(picked))
    if audit is not None:
        audit["selected"].append(np.concatenate(blocks))
        audit["kc"].append(np.asarray(kc))
    return _by_rows(functools.partial(_mix_out, p, eps=sh["eps"],
                                      residual=sh["residual"]),
                    x, jnp.concatenate(outs))


def _lightning_layer(p, x, sh, audit=None, low=()):
    nh, d = sh["l_heads"], sh["head_dim"]
    s_len = x.shape[0]
    upto = s_len if audit is None else audit["state_at"]
    outs, states = [], []
    for first in range(0, nh, HEADS):  # heads are independent: a few at a time
        n = min(HEADS, nh - first)
        q, k, v = _by_rows(
            functools.partial(_project, p, eps=sh["eps"],
                              names=("q_proj", "k_proj", "v_proj"),
                              cols=(first * d, (first + n) * d)), x)
        q = rope(rms_norm(q.reshape(s_len, n, d), p["q_norm"], sh["eps"]), sh["theta"])
        k = rope(rms_norm(k.reshape(s_len, n, d), p["k_norm"], sh["eps"]), sh["theta"])
        o, state = _lightning_scan(q, k, v.reshape(s_len, n, d), upto,
                                   first=first, total=nh, low=low)
        outs.append(o)
        states.append(np.asarray(state))
    if audit is not None:
        audit["state"].append(np.concatenate(states))
    o = rms_norm(jnp.concatenate(outs, axis=-1), p["out_norm"], sh["eps"])
    return _by_rows(functools.partial(_mix_out, p, eps=sh["eps"],
                                      residual=sh["residual"]), x, o)


@functools.partial(jax.jit, static_argnames=("eps", "div"))
def _head(norm, head, x, *, eps, div):
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, norm, eps) / div) @ _w(head)


def logits_rows(params, tokens, rows, sh: dict, audit: dict | None = None,
                low: tuple = ()):
    """(S,) tokens -> (len(rows), vocab) float32 logits at positions
    ``rows``, layer by layer.  The sequence is padded to whole blocks of
    ``ROWS`` (causal: the padding changes nothing before it).

    ``audit``, when a dict, receives what the model holds besides logits,
    as lists in layer order: ``selected``, per sparse layer the (S_padded,
    Hkv, topk) block ids each query selected (-1 where it attended
    densely); ``kc``, per sparse layer the (J, Hkv, D) compressed keys;
    ``state``, per lightning layer the (H, D, D) state after the first
    ``audit["state_at"]`` tokens (default: all the real ones; rows from
    there on are then not to be asked for).

    ``low`` names what is computed in the precision below the
    configuration's (bf16 weights and activations, float32 selection and
    state); all of ``LOW`` is the reading the serving check has to refuse:
    ``"weights"`` in float8, ``"scores"`` of the selection from bf16
    operands, a bf16 lightning ``"state"``."""
    tokens = np.asarray(tokens, np.int32)
    if audit is not None:
        audit.setdefault("state_at", len(tokens))
        audit.update(selected=[], kc=[], state=[])
    pad = -len(tokens) % ROWS
    toks = jnp.asarray(np.concatenate([tokens, np.zeros(pad, np.int32)]))
    x = params["embed"]["embedding"][toks].astype(jnp.float32) * sh["scale_emb"]
    for i, mixer in enumerate(sh["mixers"]):
        p = params[f"block_{i}"]
        if "weights" in low:
            p = _fp8_weights(p)
        layer = _sparse_layer if mixer == "minicpm4" else _lightning_layer
        x = layer(p, x, sh, audit, low)
        x = _by_rows(functools.partial(_mlp, p, eps=sh["eps"],
                                       residual=sh["residual"]), x)
    head = _fp8_weights(params["logits"]) if "weights" in low else params["logits"]
    return _head(params["norm_out"], head, x[jnp.asarray(rows)],
                 eps=sh["eps"], div=sh["logit_div"])
