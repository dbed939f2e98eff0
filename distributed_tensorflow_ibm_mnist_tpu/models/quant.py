"""Weight-only int8 quantization for the serving decode path (ISSUE 12).

Decode is weight-bandwidth-bound: every step streams the full parameter
set from HBM and does ~2 FLOPs per byte with it (PERF.md section 5: the
decode window against its weight-read roofline).  Storing the matmul weights as int8 with per-OUTPUT-CHANNEL
symmetric f32 scales cuts that dominant stream ~4x vs f32 masters (~2x vs
the bf16 compute-dtype copy) at a bounded accuracy cost — the same move
the int8 KV cache (models/transformer.py::quantize_kv_int8) made for the
cache stream in round 5, now applied to the weights.

Scheme
------
For a 2-D kernel ``W`` (in, out): ``scale[o] = max_i |W[i, o]| / 127``,
``W_q = round(W / scale)`` stored int8, ``scale`` kept f32.  Per-output-
channel (not per-tensor) so one outlier column cannot flatten every other
column's resolution, and — the tensor-parallel reason — so the scale
vector partitions EXACTLY like the kernel's output features:

* column-parallel kernels (``qkv``/``q_proj``/``kv_proj``/even
  ``dense_i``: ``P(None, tp)``) shard their scales ``P(tp)`` — each chip
  dequantizes its own output slice;
* row-parallel kernels (``proj``/odd ``dense_i``/``logits``:
  ``P(tp, None)``) keep output features whole per chip, so their scales
  REPLICATE — and because the scale is uniform over the contraction axis
  it distributes over the psum (``sum_chips(partial) * scale`` ==
  ``sum_chips(partial * scale)``), which is what makes quant compose with
  the Megatron splits without touching the reduction structure.

The dequant never materializes a full-precision weight copy:
:class:`Int8Dense` feeds the int8 kernel into the contraction as the
compute dtype (int8 -> bf16 is EXACT — every value in [-127, 127] is
representable), accumulates in f32 (``preferred_element_type``), and
applies the scale post-contraction — one multiply per output element, 1/d_in
the cost of scaling the weight itself.  The HBM stream stays int8-sized.

What is NOT quantized: embeddings (a gather, not a matmul — and the tied
head ``embed.attend`` shares the same table), norm scales/biases, biases,
and MoE expert weights (3-D einsum leaves routed by ``MoEBlock``; a
follow-on).  :func:`quantize_params_int8` passes all of these through
untouched, so a tied-embedding or MoE model quantizes its blocks and
keeps the rest at full precision — documented, never silent: the leaf
report is in the returned tree itself (int8 kernels + ``scale`` siblings
exactly where the quant model expects them).
"""

from __future__ import annotations

import re
from collections.abc import Mapping
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

# module names whose 2-D `kernel` the serving decode path quantizes —
# exactly the names megatron_rule (parallel/tensor_parallel.py) shards,
# so the inserted `scale` siblings land where the sharding rule expects
_QUANT_MODULE = re.compile(r"qkv|q_proj|kv_proj|proj|dense_\d+|logits|fc\d*")


def quantize_kernel_int8(w):
    """(in, out) kernel -> (int8 kernel, (out,) f32 scale), symmetric
    per-output-channel: ``scale = max|W[:, o]| / 127`` (floored so an
    all-zero column quantizes to zeros instead of NaN)."""
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(wf / scale).astype(jnp.int8)
    return q, scale


def quantize_params_int8(params):
    """Host/device param tree -> the quant model's tree: every 2-D
    ``kernel`` under a quantizable module name is replaced by an int8
    kernel plus a ``scale`` sibling; every other leaf passes through
    unchanged (embeddings, norms, biases, MoE experts).

    Idempotent: kernels already stored int8 (with their ``scale``
    sibling present) pass through, so the engine can call this
    unconditionally at upload AND at every ``swap_params`` — a caller
    handing an already-quantized tree is a no-op, not a double-round.
    """

    def walk(tree):
        out = {}
        for name, sub in tree.items():
            if isinstance(sub, Mapping):
                kern = sub.get("kernel")
                if (_QUANT_MODULE.fullmatch(name)
                        and getattr(kern, "ndim", 0) == 2):
                    if kern.dtype == jnp.int8:
                        out[name] = dict(sub)  # already quantized
                        continue
                    q, s = quantize_kernel_int8(kern)
                    new = {k: v for k, v in sub.items() if k != "scale"}
                    new["kernel"] = q
                    new["scale"] = s
                    out[name] = new
                else:
                    out[name] = walk(sub)
            else:
                out[name] = sub
        return out

    return walk(params)


def is_quantized(params) -> bool:
    """True when the tree holds at least one int8 kernel with its
    ``scale`` sibling — the quant model's storage layout."""
    found = False

    def walk(tree):
        nonlocal found
        for name, sub in tree.items():
            if isinstance(sub, Mapping):
                kern = sub.get("kernel")
                if (getattr(kern, "dtype", None) == jnp.int8
                        and "scale" in sub):
                    found = True
                else:
                    walk(sub)

    walk(params)
    return found


class Int8Dense(nn.Module):
    """Drop-in ``nn.Dense`` with int8-stored weights and fused dequant.

    Declares ``kernel`` (int8, (in, out)), ``scale`` (f32, (out,)), and
    ``bias`` (f32, (out,)) under the SAME module name its full-precision
    sibling would use, so :func:`quantize_params_int8` output binds by
    name and ``megatron_rule`` path-matching applies unchanged.  The
    contraction runs int8-as-compute-dtype x activation with f32
    accumulation; the per-channel scale (and the bias, still f32) apply
    post-contraction in f32, then the result drops back to the compute
    dtype — strictly MORE accurate than ``nn.Dense``'s bias-add in bf16.

    Init gives zero kernels / unit scales: structurally valid (shape and
    dtype probes, ``model.init`` in tests), numerically meaningless — real
    weights always arrive via :func:`quantize_params_int8` at the
    engine's upload/swap seams.
    """

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_in = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.zeros, (d_in, self.features), jnp.int8)
        scale = self.param(
            "scale", nn.initializers.ones, (self.features,), jnp.float32)
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32)
        x = x.astype(self.dtype)
        # int8 -> compute dtype inside the contraction: XLA fuses the
        # convert into the matmul read, so HBM traffic stays int8-sized
        y = jnp.einsum(
            "...i,io->...o", x, kernel.astype(self.dtype),
            preferred_element_type=jnp.float32)
        y = y * scale + bias
        return y.astype(self.dtype)


def weight_stream_bytes(params) -> int:
    """Total parameter bytes one decode step streams from HBM — the
    honest bytes-moved figure tests/test_quant.py pins at 3.2-4x (int8 kernels
    count 1 byte/element, their f32 scales 4, everything else its own
    itemsize)."""
    return sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
