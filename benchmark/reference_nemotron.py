"""The plain reference of Nemotron-3-Super as this repo's ``NemotronHLM``
runs it: straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, no kernels, no cache, no batching, one layer at a time over one
sequence, in blocks of rows and with each matrix cast as it is used.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json
(the catalog's row: ``config``, ``model_type`` nemotron_h).  ``x`` a
layer's input; every RMSNorm with a learned scale and ``layer_norm_epsilon``
1e-5; no biases but the convolution's (``use_conv_bias``)::

    h0 = E[tok];  h += F_l(RMSNorm(h)), F_l by hybrid_override_pattern[l]
    logits = W_head RMSNorm(h)                               (head untied)

``M`` (Mamba-2, 128 heads of 64, state 128, 8 groups, conv 4, expand 2):
``[z | xBC | dt] = W_in u`` (8192 | 8192 + 2 x 8 x 128 | 128); ``xBC =
silu(causal depthwise conv_4(xBC) + b)`` split into x (128 x 64), B and C
(8 x 128; head i reads group i // 16); ``dt = softplus(dt + dt_bias)``, ``A
= -exp(A_log)``; the recurrence runs TOKEN BY TOKEN here (``lax.scan``, no
chunking; over blocks of ``ROWS`` rows, the state and the convolution's last
inputs carried from block to block)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T    (64 x 128 a head, float32)
    y_t = S_t C_t + D x_t

``F = W_out RMSNorm_groups(y * silu(z))``, the norm per group of 1024
channels.  ``*``: grouped-query attention, 32 query and 2 KV heads of 128,
scores / sqrt(128), full causal softmax, no rotary embedding (assumed).
``E`` (LatentMoE): ``s = sigmoid(W_r u)`` in float32, 512 wide; the 22 with
the largest ``s + b`` (``noaux_tc``, one group); ``w = 5 s_sel / sum
s_sel``; ``F = W_lat_up sum_e w_e W2_e relu(W1_e (W_lat_down u))^2 + W2_s
relu(W1_s u)^2`` (latent 1024, experts 2688, shared 5376).  The routed sum
runs over the same ``held`` experts as the program (``held_first`` and as
many as the banks hold), computed DENSELY for every row with the rows that
did not choose an expert weighted 0: the partial sum of one chip of the
deployment, as the program's.  Given ``forced`` ids (the program's own
choices), a token is routed to them, at this router's scores, in place of
its own top 22.

``assumed`` (also in ``configs/nemotron3-super-serve-d11.json``): no rotary
embedding in the attention layers; the router reads the full 4096-wide
``u``; a float32 SSM state; no clamp of ``dt`` beyond the softplus; no MTP
layer; the correction bias and the weights drawn from ``--seed``.

It reads the program's parameter tree by its names (``embed``,
``block_<i>/{norm, in_proj, conv_weight, conv_bias, A_log, dt_bias, D,
norm_gated, out_proj, q_proj, k_proj, v_proj, o_proj, router, router_bias,
latent_down, latent_up, experts_up, experts_down, shared_up, shared_down}``,
``norm_out``, ``logits``) and shares no code with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_mimo import QROWS, _attend_rows, _fp8
from benchmark.reference_sala import ROWS, _bf16, _by_rows, rms_norm

LOW = ("weights", "state")  # what the precision below lowers: float8
#   weights; a bf16 SSM state with bf16 step sizes and decays


def shape_of(cfg: dict) -> dict:
    """The reference's static arguments from a configuration file."""
    return {
        "pattern": cfg["hybrid_override_pattern"],
        "heads": cfg["mamba_num_heads"], "head_dim": cfg["mamba_head_dim"],
        "state": cfg["ssm_state_size"], "groups": cfg["n_groups"],
        "conv": cfg["conv_kernel"],
        "attn_heads": cfg["num_attention_heads"],
        "attn_hkv": cfg["num_key_value_heads"], "attn_dim": cfg["head_dim"],
        "top_k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]),
        "held_first": cfg["deployment"]["held_first"],
        "eps": cfg["layer_norm_epsilon"],
    }


def _mat(w, low):
    return _fp8(w) if "weights" in low else w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _in_proj(p, x, *, eps, low=()):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, p["norm"], eps) @ _mat(p["in_proj"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "low"))
def _out_proj(p, x, y, z, *, groups, eps, low=()):
    """x + W_out RMSNorm_groups(y * silu(z)) for a block of rows."""
    with jax.default_matmul_precision("highest"):
        g = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
        g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
        g = g.reshape(y.shape) * p["norm_gated"]["scale"].astype(jnp.float32)
        return x + g @ _mat(p["out_proj"]["kernel"], low)


@functools.partial(jax.jit, static_argnames=("groups", "low"))
def _ssd_scan(x, dt, a_log, b, c, state, upto, *, groups, low=()):
    """The recurrence token by token over a block of rows: x (S, H, P), dt
    (S, H), b / c (S, G, N), ``state`` (H, P, N) before the block -> y (S,
    H, P) without the skip term, the state after the block, and the state
    after its first ``upto`` tokens (the state is held there if ``upto`` <
    0 or carried through if ``upto`` >= S)."""
    with jax.default_matmul_precision("highest"):
        nh, p = x.shape[1:]
        per = nh // groups
        a = -jnp.exp(a_log.astype(jnp.float32))

        def step(carry, inp):
            state, held = carry
            i, xt, dtt, bt, ct = inp
            if "state" in low:  # the precision below: bf16 step, decay, state
                dtt = _bf16(dtt)
                decay = _bf16(jnp.exp(dtt * a))
            else:
                decay = jnp.exp(dtt * a)
            bh, ch = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)
            new = (decay[:, None, None] * state
                   + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
            if "state" in low:
                new = _bf16(new)
            held = jnp.where(i < upto, new, held)
            return (new, held), jnp.einsum("hpn,hn->hp", new, ch)

        (state, held), y = jax.lax.scan(
            step, (state, state), (jnp.arange(x.shape[0]), x, dt, b, c))
        return y, state, held


def _mamba(p, x, sh, audit, low):
    """The layer over blocks of ``ROWS`` rows, the convolution's last
    inputs and the SSM state carried from block to block."""
    s_len = x.shape[0]
    nh, hp, n, g, k = (sh[key] for key in ("heads", "head_dim", "state",
                                           "groups", "conv"))
    d_in, width = nh * hp, nh * hp + 2 * g * n
    w = p["conv_weight"].astype(jnp.float32)
    upto = audit["state_at"] if audit is not None else s_len
    state = jnp.zeros((nh, hp, n), jnp.float32)
    tail = jnp.zeros((k - 1, width), jnp.float32)
    held = held_tail = None
    out = []
    for a0 in range(0, s_len, ROWS):
        xb = x[a0:a0 + ROWS]
        rows = xb.shape[0]
        zxbcdt = _in_proj(p, xb, eps=sh["eps"], low=low)
        z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + width],
                      zxbcdt[:, d_in + width:])
        pad = jnp.concatenate([tail, xbc])
        if held_tail is None and a0 <= upto <= a0 + rows:
            # the convolution's last k - 1 inputs before position ``upto``
            held_tail = pad[upto - a0:upto - a0 + k - 1]
        tail = pad[rows:]
        u = jax.nn.silu(sum(pad[i:i + rows] * w[i] for i in range(k))
                        + p["conv_bias"].astype(jnp.float32))
        xs = u[:, :d_in].reshape(rows, nh, hp)
        b = u[:, d_in:d_in + g * n].reshape(rows, g, n)
        c = u[:, d_in + g * n:].reshape(rows, g, n)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
        y, nxt, at = _ssd_scan(xs, dt, p["A_log"], b, c, state, upto - a0,
                               groups=g, low=low)
        if held is None and a0 <= upto <= a0 + rows:
            held = at
        state = nxt
        y = (y + p["D"].astype(jnp.float32)[:, None] * xs).reshape(rows, d_in)
        out.append(_out_proj(p, xb, y, z, groups=g, eps=sh["eps"], low=low))
    if audit is not None:
        audit["ssm_state"].append(np.asarray(held))
        audit["conv_state"].append(np.asarray(held_tail))
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _qkv(p, x, *, eps, low=()):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, p["norm"], eps)
        return tuple(h @ _mat(p[name]["kernel"], low)
                     for name in ("q_proj", "k_proj", "v_proj"))


@functools.partial(jax.jit, static_argnames=("low",))
def _add_out(p, x, o, *, low=()):
    with jax.default_matmul_precision("highest"):
        return x + o @ _mat(p["o_proj"]["kernel"], low)


def _attention(p, x, sh, low):
    s_len = x.shape[0]
    nh, hkv, d = sh["attn_heads"], sh["attn_hkv"], sh["attn_dim"]
    q, k, v = _by_rows(functools.partial(_qkv, p, eps=sh["eps"], low=low), x)
    q, k, v = (q.reshape(s_len, nh, d), k.reshape(s_len, hkv, d),
               v.reshape(s_len, hkv, d))
    none = jnp.zeros((nh,), jnp.float32)
    o = jnp.concatenate([_attend_rows(q[a:a + QROWS], k, v, none, a, window=0,
                                      low=low)
                         for a in range(0, s_len, QROWS)])
    return _by_rows(functools.partial(_add_out, p, low=low), x, o)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scale", "first",
                                             "low"))
def _latent_moe(p, x, forced, *, eps, top_k, scale, first, low=()):
    """x + the held experts' routed part + the shared expert, for a block of
    rows; and the ids each row chose.  ``forced`` (rows, top_k), when not
    None, are the experts the rows are routed to in place of the chosen (a
    row of -1: its own choice); the weights are still this router's scores
    at the ids used."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, p["norm"], eps)
        s = jax.nn.sigmoid(u @ _mat(p["router"], low))
        _, ids = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), top_k)
        use = ids if forced is None else jnp.where(forced[:, :1] >= 0, forced, ids)
        w = jnp.take_along_axis(s, use, -1)
        w = scale * w / w.sum(-1, keepdims=True)
        lat = u @ _mat(p["latent_down"]["kernel"], low)

        def expert(e, acc):  # every row through held expert e, weighted
            w_col = jnp.where(use == first + e, w, 0.0).sum(-1)
            h = jnp.square(jax.nn.relu(lat @ _mat(p["experts_up"][e], low)))
            return acc + w_col[:, None] * (h @ _mat(p["experts_down"][e], low))

        routed = jax.lax.fori_loop(0, p["experts_up"].shape[0], expert,
                                   jnp.zeros_like(lat))
        shared = jnp.square(jax.nn.relu(u @ _mat(p["shared_up"]["kernel"], low)))
        return (x + routed @ _mat(p["latent_up"]["kernel"], low)
                + shared @ _mat(p["shared_down"]["kernel"], low)), ids


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(norm, head, x, *, eps, low=()):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm, eps) @ _mat(head["kernel"], low)


def logits_rows(params, tokens, rows, sh: dict, audit: dict | None = None,
                low: tuple = (), forced: list | None = None):
    """(S,) tokens -> (len(rows), vocab) float32 logits at positions
    ``rows``, layer by layer.  The sequence is padded to whole blocks of
    ``ROWS`` (causal: the padding changes nothing before it).

    ``audit``, when a dict, receives what the model holds besides logits, as
    lists in layer order: per Mamba layer ``ssm_state`` (H, P, N) and
    ``conv_state`` (conv - 1, width), the convolution's last inputs, both
    after the first ``audit["state_at"]`` tokens (default: the sequence's
    length); per expert layer ``chosen``, the (S_padded, top_k) expert ids
    each token chose.

    ``forced``, when given, is a list a expert layer of (n, top_k) expert
    ids for the first n tokens (the program's own choices): those tokens
    are routed to them whatever this router chooses (``chosen`` still holds
    what it chooses), so that a choice the two part on at a near-tie is not
    carried into every later layer; the tokens past n keep their own.

    ``low`` names what the control lowers (each of ``LOW`` is a reading the
    serving check has to refuse): ``"weights"`` in float8, the ``"state"``
    of the Mamba layers with its step sizes and decays in bf16."""
    tokens = np.asarray(tokens, np.int32)
    low = tuple(low)
    if audit is not None:
        audit.setdefault("state_at", len(tokens))
        audit.update(ssm_state=[], conv_state=[], chosen=[])
    pad = -len(tokens) % ROWS
    toks = jnp.asarray(np.concatenate([tokens, np.zeros(pad, np.int32)]))
    x = params["embed"]["embedding"][toks].astype(jnp.float32)
    layer = 0
    for i, kind in enumerate(sh["pattern"]):
        p = params[f"block_{i}"]
        if kind == "M":
            x = _mamba(p, x, sh, audit, low)
        elif kind == "*":
            x = _attention(p, x, sh, low)
        else:
            moe = functools.partial(
                _latent_moe, p, eps=sh["eps"], top_k=sh["top_k"],
                scale=sh["scale"], first=sh["held_first"], low=low)
            if forced is None:
                x, ids = _by_rows(lambda xb: moe(xb, None), x)
            else:
                own = np.full((x.shape[0], sh["top_k"]), -1, np.int32)
                n = min(len(forced[layer]), x.shape[0])
                own[:n] = np.asarray(forced[layer], np.int32)[:n]
                x, ids = _by_rows(moe, x, jnp.asarray(own))
            layer += 1
            if audit is not None:
                audit["chosen"].append(np.asarray(ids))
    return _head(params["norm_out"], params["logits"], x[jnp.asarray(rows)],
                 eps=sh["eps"], low=low)

