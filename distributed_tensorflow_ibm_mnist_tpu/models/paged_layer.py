"""The cache path of a global (full-context) attention layer whose keys and
values live in the serving engine's page pool: models/mimo.py's global
layers and models/nemotron_h.py's attention layers call it with their
widths (``Widths``) and nothing else of theirs.

A decode step (one token a row, all rows) writes each row's key and value
at its cursor's page (a row that is not decoding writes to the trash page)
and reads the row's live pages through the paged kernel
(``ops/paged_attention.py``).  A prefill chunk (ONE row at its cursor)
writes its pages, then scores the row's keys ``KEY_BLOCK`` at a time up to
the chunk's end under an online softmax.  Keys narrower than a whole number
of 128 lanes are stored zero-padded to one (192 -> 256: the kernel reads
whole lane rows; q is padded alike, so the scores are the same).  Every
function takes the calling module for its cache variables (``pages_k``,
``pages_v``, ``block_table``), so the leaves sit under the layer's name.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_tensorflow_ibm_mnist_tpu.models.sala import _external
from distributed_tensorflow_ibm_mnist_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_kernel_eligible,
)

LANES = 128
MASK = -1e30
KEY_BLOCK = 512  # keys a step of a chunk's attention scores at once


class Widths(NamedTuple):
    heads: int          # query heads
    heads_kv: int
    head_dim: int       # of q and k
    v_head_dim: int
    page_size: int
    dtype: Any          # the compute dtype


def lane_pad(d: int) -> int:
    return -(-d // LANES) * LANES


def padded(x, head_dim: int):
    """The last axis zero-padded from ``head_dim`` to whole lanes."""
    pad = lane_pad(head_dim) - head_dim
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def attend(q, k, v, allow, head_dim: int, sink=None):
    """softmax(q.k / sqrt(head_dim) [+ the sink's column]) v under
    ``allow``.  q (..., Q, H, Dk), k (..., N, Hkv, Dk), v (..., N, Hkv,
    Dv), allow broadcastable to (..., Q, N) -> (..., Q, H, Dv)."""
    hkv = k.shape[-2]
    g = q.shape[-2] // hkv
    qg = q.reshape(q.shape[:-2] + (hkv, g, q.shape[-1]))
    sc = jnp.einsum("...qkgd,...nkd->...kgqn", qg, k,
                    preferred_element_type=jnp.float32) * head_dim ** -0.5
    sc = jnp.where(allow[..., None, None, :, :], sc, MASK)
    m = sc.max(-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(hkv, g, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(sc - m)
    den = p.sum(-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - m)
    o = jnp.einsum("...kgqn,...nkd->...qkgd", (p / den).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(o.shape[:-3] + (hkv * g, v.shape[-1]))


def pool(module, max_len: int, w: Widths):
    """The layer's ``pages_k``, ``pages_v`` variables and block table."""
    ps = w.page_size
    if max_len % ps:
        raise ValueError(
            f"max_len ({max_len}) must be a multiple of page_size ({ps})")
    pages_k = module.variable("cache", "pages_k", _external("pages_k"))
    pages_v = module.variable("cache", "pages_v", _external("pages_v"))
    if not paged_kernel_eligible(w.dtype, pages_k.value.dtype, ps, w.heads_kv,
                                 pages_k.value.shape[-1], w.v_head_dim):
        raise ValueError(
            "global-layer decode reads the pool through the paged kernel "
            "only (ops.paged_attention.paged_kernel_eligible): pool "
            f"{pages_k.value.dtype} for compute {w.dtype}, page {ps}, "
            f"{w.heads_kv} KV heads of {pages_k.value.shape[-1]} / "
            f"{w.v_head_dim}")
    bt = module.variable("cache", "block_table", _external("block_table")).value
    return pages_k, pages_v, bt


def paged_step(module, q, k, v, idx, n_valid, max_len: int, w: Widths):
    """One token a row, all rows: write it to its page (a row that is not
    decoding writes to the trash page), read the row's live pages."""
    pages_k, pages_v, bt = pool(module, max_len, w)
    ps = w.page_size
    t = jnp.minimum(idx, max_len - 1)
    page = jnp.where(n_valid > 0,
                     jnp.take_along_axis(bt, (t // ps)[:, None], 1)[:, 0], 0)
    pages_k.value = pages_k.value.at[page, t % ps].set(padded(k[:, 0], w.head_dim))
    pages_v.value = pages_v.value.at[page, t % ps].set(v[:, 0])
    o = paged_decode_attention(
        padded(q[:, 0], w.head_dim), pages_k.value, pages_v.value, bt, t + 1,
        scale=w.head_dim ** -0.5)
    return o[:, None]


def paged_chunk(module, q, k, v, idx, max_len: int, w: Widths):
    """ONE row's prefill chunk at its cursor: write its pages, then score
    the row's keys ``KEY_BLOCK`` at a time up to the chunk's end (the row's
    cursor, never ``max_len``) under an online softmax."""
    pages_k, pages_v, bt = pool(module, max_len, w)
    ps = w.page_size
    b, c = q.shape[:2]
    kb = min(KEY_BLOCK, c)
    if b != 1 or c % kb or kb % ps:
        raise ValueError(
            f"a prefill chunk is one row of whole key blocks ({kb}) of "
            f"whole pages ({ps}), got {b} rows of {c} tokens")
    start = idx[0]
    pos = jnp.minimum(start + jnp.arange(c), max_len - 1)
    page = bt[0, pos // ps]
    pages_k.value = pages_k.value.at[page, pos % ps].set(padded(k[0], w.head_dim))
    pages_v.value = pages_v.value.at[page, pos % ps].set(v[0])
    hkv, dv = w.heads_kv, w.v_head_dim
    g = w.heads // hkv
    qg = padded(q[0], w.head_dim).reshape(c, hkv, g, -1)
    qpos = start + jnp.arange(c)
    per = kb // ps

    def block(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(bt[0], j * per, per)
        kj = pages_k.value[ids].reshape(kb, hkv, -1)
        vj = pages_v.value[ids].reshape(kb, hkv, dv)
        sc = jnp.einsum("qkgd,nkd->kgqn", qg, kj,
                        preferred_element_type=jnp.float32) * w.head_dim ** -0.5
        kpos = j * kb + jnp.arange(kb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, MASK)
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "kgqn,nkd->kgqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((hkv, g, c, 1), -jnp.inf, jnp.float32),
            jnp.zeros((hkv, g, c, 1), jnp.float32),
            jnp.zeros((hkv, g, c, dv), jnp.float32))
    n_blocks = jnp.minimum(start + c, max_len) // kb
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)
    return (acc / l).transpose(2, 0, 1, 3).reshape(1, c, w.heads, dv)
