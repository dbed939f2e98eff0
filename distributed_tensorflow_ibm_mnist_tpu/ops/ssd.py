"""Mamba-2's state-space duality (SSD): a per-head state updated by a decay
that changes with every token, in place of a key/value cache.

Per head ``h`` (``P``-wide inputs, ``N``-wide state, ``B``/``C`` shared by
the heads of a group)::

    S_t = exp(a_t) S_{t-1} + (dt_t x_t) B_t^T     (P x N, float32)
    y_t = S_t C_t
    a_t = dt_t A_h <= 0,   dt_t = softplus(...) > 0,   A_h = -exp(A_log_h)

Two forms of the same recurrence:

* :func:`ssd_chunk_scan` — a Pallas TPU kernel for a chunk of ``T`` tokens
  of ONE row.  It takes the row's state and returns the chunk's outputs and
  the row's next state.  The grid is (heads, sub-chunks of ``_SUB``
  tokens); with ``c_i`` the cumulative sum of ``a`` inside the sub-chunk
  (taken on the MXU against a triangle of ones), a sub-chunk is

      Y      = ((C B^T) * L) X  +  exp(c) * (C S^T)
      S_next = exp(c_last) S + (X * exp(c_last - c))^T B

  with ``X = dt * x``, ``L[i, j] = exp(c_i - c_j)`` for ``j <= i`` and 0
  above.  Every exponent is a sum of non-positive ``a``: nothing can
  overflow.  The caller zeroes ``a`` and ``X`` past the row's real tokens,
  so a padded tail decays nothing and contributes nothing.  The state rides
  in VMEM scratch from one sub-chunk to the next.  All products are float32
  at ``HIGHEST`` precision, as in ``ops/lightning_attention.py``: a bf16
  state or bf16 decays are what the serving check is built to refuse.
* :func:`ssd_step` — the decode window's one-token update over all rows,
  also a Pallas kernel (so the trace names it): each row's state is read
  and written once, in place (the output aliases the input), a row a grid
  step, on the vector unit: a head's ``dt x`` is a column broadcast along
  the state's lanes and ``y`` a lane reduction (the outer products as
  matrix products took 3.95 ms a call against 1.96 at 128 rows, 1.31 at
  the roofline, on a v5e).  A row that is not decoding keeps its state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret

_SUB = 256  # tokens per sub-chunk: (256, 256) decay tile
_HI = lax.Precision.HIGHEST


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _scan_kernel(a_ref, x_ref, b_ref, c_ref, s0_ref, y_ref, s_out_ref, s_sc,
                 *, sub):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_sc[...] = s0_ref[...]

    a = a_ref[...]                                    # (1, L)
    x = x_ref[...]                                    # (L, P), dt * x
    bm = b_ref[...].astype(jnp.float32)               # (L, N)
    cm = c_ref[...].astype(jnp.float32)               # (L, N)
    s = s_sc[...]                                     # (P, N)
    i = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    j = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    tri = (j <= i).astype(jnp.float32)
    cum_col = _dot(tri, a, ((1,), (1,)))              # (L, 1): sum_{j<=i} a_j
    cum_row = _dot(a, tri, ((1,), (1,)))              # (1, L)
    seg = jnp.where(j <= i, jnp.exp(jnp.minimum(cum_col - cum_row, 0.0)), 0.0)
    y = _dot(_dot(cm, bm, ((1,), (1,))) * seg, x, ((1,), (0,)))
    y = y + jnp.exp(cum_col) * _dot(cm, s, ((1,), (1,)))
    y_ref[...] = y
    last = cum_col[sub - 1:sub, :]                    # (1, 1)
    w = jnp.exp(jnp.minimum(last - cum_col, 0.0))     # (L, 1)
    s = jnp.exp(last) * s + _dot(x * w, bm, ((0,), (0,)))
    s_sc[...] = s

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out_ref[...] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_chunk_scan(a, x, b, c, state, interpret):
    h, t, p = x.shape
    g, _, n = b.shape
    per = h // g
    sub = _SUB if t % _SUB == 0 else t
    xy = pl.BlockSpec((None, sub, p), lambda hh, cc: (hh, cc, 0))
    bc = pl.BlockSpec((None, sub, n), lambda hh, cc: (hh // per, cc, 0))
    st = pl.BlockSpec((None, p, n), lambda hh, cc: (hh, 0, 0))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, sub=sub),
        grid=(h, t // sub),
        in_specs=[pl.BlockSpec((None, 1, sub), lambda hh, cc: (hh, 0, cc)),
                  xy, bc, bc, st],
        out_specs=[xy, st],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((h, t, p), jnp.float32),
                   jax.ShapeDtypeStruct((h, p, n), jnp.float32)],
        name="ssd_chunk_scan",
        **({"interpret": True} if interpret else {
            "interpret": False,
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"))}),
    )(a[:, None, :], x, b, c, state)
    return y, s


def ssd_chunk_scan(x, dt, a_log, b, c, state, n_valid,
                   interpret: bool | None = None):
    """One row's chunk through the SSD recurrence.

    ``x`` (H, T, P), ``dt`` (H, T) float32 step sizes (after the softplus),
    ``a_log`` (H,), ``b`` / ``c`` (G, T, N) (head ``h`` reads group
    ``h // (H / G)``), ``state`` (H, P, N) float32 as the row left it,
    ``n_valid`` the count of real tokens (``T`` a multiple of 256, or any one
    sub-chunk).  Positions at and past ``n_valid`` get outputs nobody reads
    and leave the state alone.  Returns ``(y (H, T, P) float32 without the
    skip term, next state (H, P, N) float32)``."""
    real = jnp.arange(x.shape[1]) < n_valid
    dt = jnp.where(real[None, :], dt.astype(jnp.float32), 0.0)
    a = dt * -jnp.exp(a_log.astype(jnp.float32))[:, None]
    return _ssd_chunk_scan(a, x.astype(jnp.float32) * dt[..., None], b, c,
                           state, interpret=resolve_interpret(interpret))


def _step_kernel(live_ref, a_ref, x_ref, b_ref, c_ref, s_ref, y_ref, s_out_ref,
                 *, groups, per):
    keep = live_ref[pl.program_id(0)] > 0

    def group(g, carry):
        xg = x_ref[g]                                   # (P, per): dt * x
        decay = jnp.exp(a_ref[g])                       # (1, per)
        bm = b_ref[pl.ds(g, 1), :]                      # (1, N)
        cm = c_ref[pl.ds(g, 1), :]
        for hh in range(per):
            h = g * per + hh
            s = s_ref[h]                                # (P, N)
            new = decay[:, hh:hh + 1] * s + xg[:, hh:hh + 1] * bm
            s_out_ref[h] = jnp.where(keep, new, s)
            y_ref[g, :, hh:hh + 1] = jnp.sum(new * cm, axis=1, keepdims=True)
        return carry

    lax.fori_loop(0, groups, group, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_step(live, a, x, b, c, state, interpret):
    rows, h, p, n = state.shape
    g = b.shape[1]
    per = h // g
    # a group's heads along lanes: a head's x is a column of the (P, per)
    # tile, broadcast along the state's N lanes with no matrix product
    y, s = pl.pallas_call(
        functools.partial(_step_kernel, groups=g, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[
                pl.BlockSpec((None, g, 1, per), lambda r, *_: (r, 0, 0, 0)),
                pl.BlockSpec((None, g, p, per), lambda r, *_: (r, 0, 0, 0)),
                pl.BlockSpec((None, g, n), lambda r, *_: (r, 0, 0)),
                pl.BlockSpec((None, g, n), lambda r, *_: (r, 0, 0)),
                pl.BlockSpec((None, h, p, n), lambda r, *_: (r, 0, 0, 0))],
            out_specs=[
                pl.BlockSpec((None, g, p, per), lambda r, *_: (r, 0, 0, 0)),
                pl.BlockSpec((None, h, p, n), lambda r, *_: (r, 0, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((rows, g, p, per), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        name="ssd_step",
        **({"interpret": True} if interpret else {
            "interpret": False,
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel",), vmem_limit_bytes=48 << 20)}),
    )(live, a.reshape(rows, g, 1, per),
      x.reshape(rows, g, per, p).transpose(0, 1, 3, 2), b, c, state)
    return y.transpose(0, 1, 3, 2).reshape(rows, h, p), s


def ssd_step(x, dt, a_log, b, c, state, live, interpret: bool | None = None):
    """The decode window's one-token SSD update over all rows.

    ``x`` (B, H, P), ``dt`` (B, H) float32 step sizes, ``a_log`` (H,),
    ``b`` / ``c`` (B, G, N), ``state`` (B, H, P, N) float32 (updated in
    place), ``live`` (B,) bool: a row that is not decoding (idle, or still
    prefilling) keeps its state.  Returns ``(y (B, H, P) float32 without
    the skip term, state)``."""
    dt = dt.astype(jnp.float32)
    a = dt * -jnp.exp(a_log.astype(jnp.float32))[None, :]
    return _ssd_step(live.astype(jnp.int32), a,
                     x.astype(jnp.float32) * dt[..., None],
                     b.astype(jnp.float32), c.astype(jnp.float32), state,
                     interpret=resolve_interpret(interpret))


def causal_conv(u, conv_state, weight, bias, n_valid):
    """A causal depthwise convolution over ``K`` taps, carried across calls.

    ``u`` (B, T, C) this call's inputs, ``conv_state`` (B, K - 1, C) the
    row's last ``K - 1`` inputs before them (zeros before position 0),
    ``weight`` (K, C) (tap ``K - 1`` multiplies the current input), ``bias``
    (C,), ``n_valid`` (B,) the real inputs of each row.  Returns ``(out (B,
    T, C) float32 before the activation, next conv state (B, K - 1, C))``:
    the last ``K - 1`` REAL inputs, reaching back into the old state when a
    row has fewer than ``K - 1`` of them; a row with none keeps its state."""
    k = weight.shape[0]
    t = u.shape[1]
    xs = jnp.concatenate([conv_state.astype(u.dtype), u], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(xs[:, i:i + t].astype(jnp.float32) * w[i] for i in range(k))
    nxt = jax.vmap(lambda row, nv: lax.dynamic_slice_in_dim(row, nv, k - 1))(
        xs, n_valid)
    return out + bias.astype(jnp.float32), nxt.astype(conv_state.dtype)
