"""Lightning (linear) attention: a per-head decayed outer-product state in
place of a key/value cache.

    S_t = lam_h * S_{t-1} + k_t^T v_t        (D x D, float32)
    o_t = (q_t / sqrt(D)) S_t
    lam_h = exp(-slope_h),  slope_h = 2^(-8 (h + 1) / H)

Two forms of the same recurrence:

* :func:`lightning_chunk_scan` — a Pallas TPU kernel for a chunk of ``T``
  tokens of ONE row.  It takes the row's state and returns the chunk's
  outputs and the row's next state.  The grid is (heads, sub-chunks of
  ``_SUB`` tokens); a sub-chunk is three small matrix products,

      O      = ((Q K^T) * Dmat) V  +  lam^(i+1) Q S
      S_next = lam^n S + (K * lam^(n-1-j))^T V

  with ``Dmat[i, j] = lam^(i-j)`` for ``j <= i`` and ``n`` the sub-chunk's
  count of real tokens (a padded tail contributes nothing and decays
  nothing).  Every exponent is non-negative, so nothing overflows whatever
  the slope.  The state rides in VMEM scratch from one sub-chunk to the next.
  All products are float32 at ``HIGHEST`` precision: at D = 128 the kernel
  is a few hundred MFLOP a sub-chunk, far below what the chunk's projections
  cost, and a bf16 state or bf16 decays are what the serving check is built
  to refuse.
* :func:`lightning_step` — the one-token recurrence of the decode window,
  plain ``jax.numpy`` over all rows at once (it is a read and a write of the
  state, nothing a kernel could save).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret

_SUB = 256  # tokens per sub-chunk: (256, 256) decay tile, 64 vregs
_HI = lax.Precision.HIGHEST


def lightning_slopes(heads: int) -> jnp.ndarray:
    """(H,) float32 decay slopes of Lightning Attention: 2^(-8 (h+1) / H)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / heads)


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _scan_kernel(nv_ref, slope_ref, q_ref, k_ref, v_ref, s0_ref, o_ref,
                 s_out_ref, s_sc, *, sub, scale):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_sc[...] = s0_ref[...]

    slope = slope_ref[0:1, 0:1]  # (1, 1), this head's
    n = jnp.clip(nv_ref[0] - c * sub, 0, sub)  # real tokens in the sub-chunk
    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = s_sc[...]
    i = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    j = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    dmat = jnp.where(
        (j <= i) & (j < n),
        jnp.exp(-slope * jnp.maximum(i - j, 0).astype(jnp.float32)), 0.0)
    row = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    o = _dot(_dot(q, k, ((1,), (1,))) * dmat, v, ((1,), (0,)))
    o = o + jnp.exp(-slope * (row + 1).astype(jnp.float32)) * _dot(
        q, s, ((1,), (0,)))
    o_ref[...] = o.astype(o_ref.dtype)
    kd = jnp.where(
        row < n,
        jnp.exp(-slope * jnp.maximum(n - 1 - row, 0).astype(jnp.float32)), 0.0)
    s = jnp.exp(-slope * n.astype(jnp.float32)) * s + _dot(
        k * kd, v, ((0,), (0,)))
    s_sc[...] = s

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out_ref[...] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lightning_chunk_scan(q, k, v, state, slopes, n_valid, interpret):
    h, t, d = q.shape
    sub = _SUB if t % _SUB == 0 else t
    qkv = pl.BlockSpec((None, sub, d), lambda hh, c, *_: (hh, c, 0))
    st = pl.BlockSpec((None, d, d), lambda hh, c, *_: (hh, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_scan_kernel, sub=sub, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h, t // sub),
            in_specs=[pl.BlockSpec((None, 1, 128), lambda hh, c, *_: (hh, 0, 0)),
                      qkv, qkv, qkv, st],
            out_specs=[qkv, st],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((h, d, d), jnp.float32)],
        name="lightning_chunk_scan",
        **({"interpret": True} if interpret else {
            "interpret": False,
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"))}),
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32),
      jnp.broadcast_to(slopes.astype(jnp.float32)[:, None, None], (h, 1, 128)),
      q, k, v, state)
    return o, s


def lightning_chunk_scan(q, k, v, state, slopes, n_valid,
                         interpret: bool | None = None):
    """One row's chunk through the lightning recurrence.

    ``q``/``k``/``v`` (H, T, D) in the compute dtype (``T`` a multiple of
    256, or any one sub-chunk), ``state`` (H, D, D) float32 as the row left
    it, ``slopes`` (H,), ``n_valid`` the count of real tokens (positions at
    and past it are padding: they get outputs nobody reads and leave the
    state alone).  Returns ``(o (H, T, D), next state (H, D, D) float32)``.
    """
    return _lightning_chunk_scan(q, k, v, state, slopes, n_valid,
                                 interpret=resolve_interpret(interpret))


def lightning_step(q, k, v, state, slopes, live):
    """The decode window's one-token recurrence over all rows.

    ``q``/``k``/``v`` (B, H, D), ``state`` (B, H, D, D) float32, ``live``
    (B,) bool: a row that is not decoding (idle, or still prefilling) keeps
    its state untouched.  Returns ``(o (B, H, D) float32, state)``."""
    d = q.shape[-1]
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    new = lam * state + k32[..., :, None] * v32[..., None, :]
    o = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32) * d ** -0.5, new,
                   precision=_HI)
    return o, jnp.where(live[:, None, None, None], new, state)
