"""Pallas TPU kernel: fused softmax cross-entropy (fwd + custom VJP bwd).

TPU-native replacement for the reference's
``tf.nn.softmax_cross_entropy_with_logits`` (SURVEY.md §2.1 "MNIST CNN model
graph" row), which it consumed as a cuDNN/Eigen kernel via the TF wheel
(SURVEY.md §2.2).  Here the whole loss — row max, exp, reduce, log, label
gather — is one VMEM-resident Pallas kernel per (row-tile, class) block, so
the logits are read from HBM exactly once in the forward and once in the
backward pass.

Shapes are padded to TPU tiling (rows → multiple of 8, classes → multiple of
128) with a large-negative fill so padded classes carry ~0 probability mass.
The public entry ``softmax_xent(logits, labels)`` returns per-example losses
(reduce outside) and differentiates via ``jax.custom_vjp``.  The CPU test
suite exercises the same code path through the Pallas interpreter, chosen
explicitly (ops/interpret.py) — never guessed from the backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret

_NEG = -1e30  # fill for padded class columns: exp(_NEG - max) == 0


def _pad_amounts(n_rows: int, n_cols: int, row_tile: int) -> tuple[int, int]:
    pad_r = (-n_rows) % row_tile
    pad_c = (-n_cols) % 128
    return pad_r, pad_c


def _fwd_kernel(logits_ref, labels_ref, loss_ref):
    """Per-block: loss[i] = logsumexp(logits[i]) - logits[i, labels[i]]."""
    logits = logits_ref[:].astype(jnp.float32)
    labels = labels_ref[:]  # (TB, 1) int32
    row_max = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - row_max
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    lse = jnp.log(sumexp) + row_max  # (TB, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked = jnp.sum(jnp.where(cols == labels, logits, 0.0), axis=-1, keepdims=True)
    loss_ref[:] = lse - picked


def _bwd_kernel(logits_ref, labels_ref, g_ref, grad_ref):
    """grad = (softmax(logits) - onehot(labels)) * g   (per row)."""
    logits = logits_ref[:].astype(jnp.float32)
    labels = labels_ref[:]
    g = g_ref[:]  # (TB, 1)
    row_max = jnp.max(logits, axis=-1, keepdims=True)
    exp = jnp.exp(logits - row_max)
    probs = exp / jnp.sum(exp, axis=-1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (cols == labels).astype(jnp.float32)
    grad_ref[:] = ((probs - onehot) * g).astype(grad_ref.dtype)


# A grid step holds one whole (row-tile, classes) block: classes are never
# split, so the row tile is what bounds VMEM.  256 rows of an LM vocabulary
# (32768 f32 columns) is a 32 MiB block, and Mosaic on the v5e refused its
# double-buffered 64 MiB against the 16 MiB scoped default (chip run, PR
# 21).  So the tile shrinks until a block's f32 form fits _BLOCK_BYTES; the
# kernels then need about _BLOCKS_LIVE such blocks (the pipeline's two input
# and, backward, two output buffers, plus the f32 temporaries of the
# softmax), which is requested as the scoped limit when it passes the
# default, and refused with the sizes when even 8 rows cannot fit.
_BLOCK_BYTES = 1 << 20
_BLOCKS_LIVE = 10
_VMEM_DEFAULT = 16 << 20
_VMEM_MAX = 96 << 20  # of the v5e's 128 MiB


def _row_tile(n_rows: int, n_cols: int) -> int:
    """Largest row tile (<= 256, dividing the padded rows) whose f32 block
    fits _BLOCK_BYTES; 8 — the sublane minimum — when none does."""
    for tile in (256, 128, 64, 32, 16, 8):
        if n_rows % tile == 0 and tile * n_cols * 4 <= _BLOCK_BYTES:
            return tile
    return 8


def _call_params(tile: int, n_cols: int, interpret: bool) -> dict:
    """``pallas_call`` keywords for one (tile, n_cols) block shape."""
    if interpret:
        return {"interpret": True}
    need = _BLOCKS_LIVE * tile * n_cols * 4
    if need > _VMEM_MAX:
        raise ValueError(
            f"softmax_xent: {n_cols} classes need ~{need >> 20} MiB of VMEM "
            f"at the minimum {tile}-row tile (limit {_VMEM_MAX >> 20} MiB); "
            "the kernel keeps a whole class row resident — use the optax "
            "loss (fused_xent=False) at this width")
    if need <= _VMEM_DEFAULT:
        return {"interpret": False}
    return {"interpret": False,
            "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=need)}


def _prepare(logits: jax.Array, labels: jax.Array, row_tile: int = 8):
    n, c = logits.shape
    pad_r, pad_c = _pad_amounts(n, c, row_tile)
    if pad_r or pad_c:
        logits = jnp.pad(logits, ((0, pad_r), (0, pad_c)), constant_values=_NEG)
        labels = jnp.pad(labels, ((0, pad_r),))
    tile = _row_tile(*logits.shape)
    return logits, labels.astype(jnp.int32)[:, None], tile


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_xent(logits, labels, interpret):
    loss, _ = _softmax_xent_fwd(logits, labels, interpret)
    return loss


def _softmax_xent_fwd(logits, labels, interpret):
    interpret = resolve_interpret(interpret)
    n = logits.shape[0]
    padded, labels2d, tile = _prepare(logits, labels)
    np_, cp = padded.shape
    loss = pl.pallas_call(
        _fwd_kernel,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec((tile, cp), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        **_call_params(tile, cp, interpret),
    )(padded, labels2d)
    return loss[:n, 0], (logits, labels)


def _softmax_xent_bwd(interpret, res, g):
    interpret = resolve_interpret(interpret)
    logits, labels = res
    n, c = logits.shape
    padded, labels2d, tile = _prepare(logits, labels)
    np_, cp = padded.shape
    g2d = jnp.pad(g.astype(jnp.float32), ((0, np_ - n),))[:, None]
    grad = pl.pallas_call(
        _bwd_kernel,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec((tile, cp), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, cp), logits.dtype),
        **_call_params(tile, cp, interpret),
    )(padded, labels2d, g2d)
    return grad[:n, :c], None


_softmax_xent.defvjp(_softmax_xent_fwd, _softmax_xent_bwd)


def softmax_xent(
    logits: jax.Array, labels: jax.Array, interpret: bool | None = None
) -> jax.Array:
    """Per-example softmax cross-entropy, (N, C) x (N,) int -> (N,) float32."""
    return _softmax_xent(logits, labels, interpret)


def softmax_xent_mean(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean fused cross-entropy — drop-in for the optax mean-loss call."""
    return softmax_xent(logits, labels).mean()
