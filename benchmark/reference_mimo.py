"""The plain reference of MiMo-V2-Flash as this repo's ``MimoLM`` runs it:
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernels, no cache, no batching, one layer at a time over one sequence, in
blocks of rows and with each matrix cast as it is used, so that 8k tokens
fit beside a serving engine.

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json
(the catalog's row: ``config``).  ``x`` a layer's input, ``l`` its index; no
biases (``attention_bias`` false); every RMSNorm with a learned scale and
``layernorm_epsilon`` 1e-5::

    h0 = E[tok];  h += Attn_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))
    logits = W_head RMSNorm(h)

*Attention*, kind by ``hybrid_layer_pattern[l]`` (0 global, 1 window):
q = W_q x as 64 heads of 192; k = W_k x as ``Hkv`` heads of 192; v = W_v x as
``Hkv`` heads of 128, times ``attention_value_scale`` 0.707; ``Hkv`` = 4
(``num_key_value_heads``) on a global layer, 8 (``swa_num_key_value_heads``)
on a window layer.  Rotary embedding on the first 64 dimensions of q and k
(``int(0.334 x 192)``), base ``rope_theta`` 5e6 on a global layer and
``swa_rope_theta`` 1e4 on a window layer; the other 128 dimensions pass.
Scores q.k / sqrt(192), causal; on a window layer query i sees keys j with
i - 128 < j <= i, and the softmax runs over those scores AND one learned
logit per head (the sink, ``add_swa_attention_sink_bias``), whose column is
dropped after normalisation; a global layer has no sink.  Output ``W_o``
(64 x 128 -> 4096).

*FFN*, kind by ``moe_layer_freq[l]``: 0 -> ``W_down(silu(W_gate u) * W_up
u)`` at width 16384.  1 -> router logits ``W_r u`` (4096 -> 256) in float32,
``s = sigmoid(logits)``; the 8 experts with the largest ``s + b`` (``b`` the
correction bias, ``noaux_tc``; ``n_group`` 1, so no group limit); weights
``s_e / sum of the chosen s`` (``norm_topk_prob``; ``routed_scaling_factor``
null = 1); ``y = sum_e w_e W_down_e(silu(W_gate_e u) * W_up_e u)`` at width
2048.  No shared expert.  The reference is given the same ``held`` experts
as the program (``held_first``, and as many as the banks hold) and adds up
THEIR terms only: the partial sum of one chip of the deployment is what
goes on to the next layer, in program and reference alike.

``assumed`` (also in ``configs/mimo-v2-flash-serve-d7.json``): v is scaled
right after its projection (the config names the scale, not its place); the
window holds 128 keys, the query's own included; rotary pairs are (d, d +
32) within the 64 rotated dimensions (the program's ``apply_rope``); the
sinks and ``b`` are drawn from ``--seed``, small and non-zero; the router
is float32; the three multi-token-prediction layers are left out (no key
of ``config`` describes them); random weights, greedy, no EOS.

It reads the program's parameter tree by its names (``embed``,
``block_<i>/{norm_attn, q_proj, k_proj, v_proj, o_proj, sink, norm_mlp,
mlp_gate, mlp_up, mlp_down, router, router_bias, experts_gate, experts_up,
experts_down}``, ``norm_out``, ``logits``) and shares no code with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_sala import ROWS, _bf16, _by_rows, rms_norm, rope

QROWS = 128    # queries per attention call
LOW = ("weights", "router", "sink", "bias")  # what the control lowers or drops


def shape_of(cfg: dict) -> dict:
    """The reference's static arguments from a configuration file."""
    dk = cfg["head_dim"]
    return {
        "kinds": tuple(cfg["hybrid_layer_pattern"]),
        "ffns": tuple(cfg["moe_layer_freq"]),
        "heads": cfg["num_attention_heads"],
        "hkv": (cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"]),
        "dk": dk, "dv": cfg["v_head_dim"],
        "rotary": int(cfg["partial_rotary_factor"] * dk),
        "theta": (float(cfg["rope_theta"]), float(cfg["swa_rope_theta"])),
        "window": cfg["sliding_window"], "eps": cfg["layernorm_epsilon"],
        "value_scale": cfg["attention_value_scale"],
        "top_k": cfg["num_experts_per_tok"],
        "held_first": cfg["deployment"]["held_first"],
    }


def _fp8(w):
    """A matmul kernel (or a bank of them, scaled per matrix) rounded to
    float8 (4 bits of exponent, 3 of mantissa) and back, kept as float32:
    weights in the precision below bf16."""
    w32 = w.astype(jnp.float32)
    scale = 240.0 / jnp.max(jnp.abs(w32), axis=(-2, -1), keepdims=True)
    return jax.lax.reduce_precision(w32 * scale, exponent_bits=4,
                                    mantissa_bits=3) / scale


def _mat(w, low):
    return _fp8(w) if "weights" in low else w.astype(jnp.float32)


def _rope_part(x, theta, r):
    return jnp.concatenate([rope(x[..., :r], theta), x[..., r:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "names", "low"))
def _project(p, x, *, eps, names, low=()):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["norm_attn"], eps)
        return tuple(h @ _mat(p[n]["kernel"], low) for n in names)


@functools.partial(jax.jit, static_argnames=("window", "low"))
def _attend_rows(q, k, v, sink, first, *, window, low=()):
    """Attention of the ``QROWS`` queries at positions ``first ..`` over the
    whole sequence's keys: causal, and within ``window`` when it is not 0,
    then with the sink's logit in the softmax and out of the sum."""
    with jax.default_matmul_precision("highest"):
        n_q, nh, dk = q.shape
        hkv = k.shape[1]
        qg = q.reshape(n_q, hkv, nh // hkv, dk)
        t = first + jnp.arange(n_q)
        pos = jnp.arange(k.shape[0])
        allow = pos[None, :] <= t[:, None]
        if window:
            allow = allow & (pos[None, :] > t[:, None] - window)
        sc = jnp.einsum("qkgd,nkd->qkgn", qg, k) / jnp.sqrt(jnp.float32(dk))
        sc = jnp.where(allow[:, None, None, :], sc, -jnp.inf)
        if window and "sink" not in low:
            col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(1, hkv, -1, 1),
                                   sc.shape[:3] + (1,))
            prob = jax.nn.softmax(jnp.concatenate([sc, col], -1), -1)[..., :-1]
        else:
            prob = jax.nn.softmax(sc, -1)
        return jnp.einsum("qkgn,nkd->qkgd", prob, v).reshape(n_q, -1)


@functools.partial(jax.jit, static_argnames=("low",))
def _add_out(p, x, o, *, low=()):
    with jax.default_matmul_precision("highest"):
        return x + o @ _mat(p["o_proj"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _mlp(p, x, *, eps, low=()):
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["norm_mlp"], eps)
        u = (jax.nn.silu(u @ _mat(p["mlp_gate"]["kernel"], low))
             * (u @ _mat(p["mlp_up"]["kernel"], low)))
        return x + u @ _mat(p["mlp_down"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "low"))
def _route(p, x, *, eps, top_k, low=()):
    """(u, chosen ids (S, k), weights (S, k)) of a block of rows."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["norm_mlp"], eps)
        w_r = _mat(p["router"], low)
        logits = (_bf16(u) @ _bf16(w_r)) if "router" in low else u @ w_r
        if "router" in low:
            logits = _bf16(logits)
        s = jax.nn.sigmoid(logits)
        b = 0.0 if "bias" in low else p["router_bias"].astype(jnp.float32)
        _, ids = jax.lax.top_k(s + b, top_k)
        w = jnp.take_along_axis(s, ids, -1)
        return u, ids, w / w.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("low",))
def _expert_term(gate, up, down, e, u, w_col, *, low=()):
    """``w_e * W_down_e(silu(W_gate_e u) * W_up_e u)`` for held expert
    ``e`` of the banks, over a block of rows (``w_col`` is 0 where the row
    did not choose it)."""
    with jax.default_matmul_precision("highest"):
        h = jax.nn.silu(u @ _mat(gate[e], low)) * (u @ _mat(up[e], low))
        return w_col[:, None] * (h @ _mat(down[e], low))


def _experts(p, x, sh, audit, low):
    """x + the held experts' part of the expert layer, rows by rows."""
    n_held = p["experts_gate"].shape[0]
    outs, chosen = [], []
    for a in range(0, x.shape[0], ROWS):
        xa = x[a:a + ROWS]
        u, ids, w = _route(p, xa, eps=sh["eps"], top_k=sh["top_k"], low=low)
        chosen.append(np.asarray(ids))
        y = xa
        for e in range(n_held):
            w_col = jnp.where(ids == sh["held_first"] + e, w, 0.0).sum(-1)
            y = y + _expert_term(p["experts_gate"], p["experts_up"],
                                 p["experts_down"], e, u, w_col, low=low)
        outs.append(y)
    if audit is not None:
        audit["chosen"].append(np.concatenate(chosen))
    return jnp.concatenate(outs)


def _attention(p, x, sh, kind, audit, low):
    s_len = x.shape[0]
    nh, hkv, dk, dv = sh["heads"], sh["hkv"][kind], sh["dk"], sh["dv"]
    q, k, v = _by_rows(functools.partial(
        _project, p, eps=sh["eps"], names=("q_proj", "k_proj", "v_proj"),
        low=low), x)
    q = _rope_part(q.reshape(s_len, nh, dk), sh["theta"][kind], sh["rotary"])
    k = _rope_part(k.reshape(s_len, hkv, dk), sh["theta"][kind], sh["rotary"])
    v = v.reshape(s_len, hkv, dv) * sh["value_scale"]
    if kind and audit is not None:
        end, w = audit["ring_at"], sh["window"]
        lo = max(end - w, 0)
        audit["ring_k"].append(np.asarray(k[lo:end]))
        audit["ring_v"].append(np.asarray(v[lo:end]))
    sink = p["sink"] if kind else jnp.zeros((nh,), jnp.float32)
    o = jnp.concatenate([
        _attend_rows(q[a:a + QROWS], k, v, sink, a,
                     window=sh["window"] if kind else 0, low=low)
        for a in range(0, s_len, QROWS)])
    return _by_rows(functools.partial(_add_out, p, low=low), x, o)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(norm, head, x, *, eps, low=()):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm, eps) @ _mat(head["kernel"], low)


def logits_rows(params, tokens, rows, sh: dict, audit: dict | None = None,
                low: tuple = ()):
    """(S,) tokens -> (len(rows), vocab) float32 logits at positions
    ``rows``, layer by layer.  The sequence is padded to whole blocks of
    ``ROWS`` (causal: the padding changes nothing before it).

    ``audit``, when a dict, receives what the model holds besides logits, as
    lists in layer order: ``chosen``, per expert layer the (S_padded, top_k)
    expert ids each token chose; ``ring_k`` / ``ring_v``, per window layer
    the keys and values of the last ``window`` positions before
    ``audit["ring_at"]`` (default: the sequence's end), in position order.

    ``low`` names what the control lowers or drops (all of ``LOW`` is the
    reading the serving check has to refuse): ``"weights"`` in float8, the
    ``"router"``'s logits from bf16 operands, the ``"sink"`` left out of
    the window layers' softmax, the correction ``"bias"`` left out of the
    choice."""
    tokens = np.asarray(tokens, np.int32)
    if audit is not None:
        audit.setdefault("ring_at", len(tokens))
        audit.update(chosen=[], ring_k=[], ring_v=[])
    pad = -len(tokens) % ROWS
    toks = jnp.asarray(np.concatenate([tokens, np.zeros(pad, np.int32)]))
    x = params["embed"]["embedding"][toks].astype(jnp.float32)
    for i, (kind, ffn) in enumerate(zip(sh["kinds"], sh["ffns"])):
        p = params[f"block_{i}"]
        x = _attention(p, x, sh, kind, audit, low)
        if ffn:
            x = _experts(p, x, sh, audit, low)
        else:
            x = _by_rows(functools.partial(_mlp, p, eps=sh["eps"], low=low), x)
    return _head(params["norm_out"], params["logits"], x[jnp.asarray(rows)],
                 eps=sh["eps"], low=low)
