"""The plain reference of the StarCoder2 block as this repo's ``CausalLM``
runs it: straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, no kernels, no cache, no batching tricks, one block at a time.

Per block (pre-LayerNorm, biased projections, tanh-GELU MLP, RoPE, GQA,
causal sliding window), following ``bigcode/starcoder2-3b``:

    h = LN(x);  q = h Wq + bq;  [k, v] = h Wkv + bkv
    q, k = rope(q), rope(k);  o = softmax(mask(q k^T / sqrt(d))) v
    x = x + o Wo + bo
    x = x + gelu_tanh(LN(x) W1 + b1) W2 + b2
    logits = LN(x) E^T                      (tied embeddings)

Departures from the published model, the program's own and kept here so
that the two agree: RoPE base 10000 (published 999999.44), LayerNorm
epsilon 1e-6 (published 1e-5), rotary pairs (d, d + D/2).

It reads the program's parameter tree by its names (``embed``,
``block_<i>/{norm_attn,q_proj,kv_proj,proj,norm_mlp,dense_0,dense_1}``,
``norm_out``) and shares no code with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROPE_THETA = 10000.0
LN_EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def rope(x):
    """(S, H, D): rotate pair (d, d + D/2) by pos * theta^(-2d/D)."""
    s, _, d = x.shape
    half = d // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("heads", "heads_kv", "window"))
def block(p, x, *, heads: int, heads_kv: int, window: int):
    """One block over one sequence ``x`` of shape (S, dim)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        x = x.astype(jnp.float32)
        s, dim = x.shape
        d = dim // heads
        h = layer_norm(x, p["norm_attn"])
        q = (h @ p["q_proj"]["kernel"] + p["q_proj"]["bias"]).reshape(s, heads, d)
        kv = (h @ p["kv_proj"]["kernel"] + p["kv_proj"]["bias"]).reshape(s, 2, heads_kv, d)
        q, k, v = rope(q), rope(kv[:, 0]), kv[:, 1]
        g = heads // heads_kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        mask = j <= i
        if window:
            mask &= (i - j) < window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v).reshape(s, dim)
        x = x + o @ p["proj"]["kernel"] + p["proj"]["bias"]
        h = layer_norm(x, p["norm_mlp"])
        h = jax.nn.gelu(h @ p["dense_0"]["kernel"] + p["dense_0"]["bias"],
                        approximate=True)
        return x + h @ p["dense_1"]["kernel"] + p["dense_1"]["bias"]


@jax.jit
def _embed(table, tokens):
    return table.astype(jnp.float32)[tokens]


@jax.jit
def _head(norm, table, x):
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, _f32(norm)) @ table.astype(jnp.float32).T


def logits_one(params, tokens, *, depth: int, heads: int, heads_kv: int, window: int):
    """(S,) tokens -> (S, vocab) float32 logits, block by block."""
    x = _embed(params["embed"]["embedding"], tokens)
    for i in range(depth):
        x = block(params[f"block_{i}"], x, heads=heads, heads_kv=heads_kv,
                  window=window)
    return _head(params["norm_out"], params["embed"]["embedding"], x)


@jax.jit
def _xent_sum(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).sum()


def mean_xent(params, tokens, labels, **shape) -> float:
    """Mean per-token cross-entropy over (B, S) tokens, a sequence at a time."""
    total = 0.0
    for row, lab in zip(tokens, labels):
        total += float(_xent_sum(logits_one(params, row, **shape), lab))
    return total / (tokens.shape[0] * tokens.shape[1])


def shape_of(cfg: dict, window: int | None = None) -> dict:
    """The reference's static shape arguments from a configuration file."""
    return {"depth": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
            "heads_kv": cfg["num_key_value_heads"],
            "window": int(cfg.get("sliding_window") or 0) if window is None else window}
