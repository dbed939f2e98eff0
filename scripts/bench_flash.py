"""Microbenchmark for ops/flash_attention on the real chip.

Times fwd and fwd+bwd at the zoo's LM shapes.  Timing fence is a
``jax.device_get`` of a scalar reduced from the output (one readback per
timed window; ``block_until_ready`` fences as well on the v5e — measured
185.5 TFLOP/s on a bf16 matmul chain with it, PR 21).

Usage: python scripts/bench_flash.py [--dtype bf16|f32] [--s 8192]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import flash_attention


def _fence(x):
    return float(jax.device_get(jnp.sum(x.astype(jnp.float32))))


def bench(fn, args, iters=5, warmup=2):
    for _ in range(warmup):
        _fence(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - t0) / iters


def attn_flops(b, s, h, d, causal):
    """Model-FLOPs convention of utils/flops.attention_flops (fwd 4BS^2HD,
    fwd+bwd 3x, causal halved) so TFLOP/s here and Trainer MFU agree."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.flops import attention_flops

    return (
        attention_flops(b, s, h, d, causal=causal, with_backward=False),
        attention_flops(b, s, h, d, causal=causal, with_backward=True),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--b", type=int, default=4)
    ap.add_argument("--s", type=int, default=8192)
    ap.add_argument("--h", type=int, default=8)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--bq", type=int, default=0)
    ap.add_argument("--bk", type=int, default=0)
    ap.add_argument("--impl", default="flash", choices=["flash", "vanilla"])
    args = ap.parse_args()

    import distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention as fa

    if args.bq:
        fa._BLOCK_Q = args.bq
    if args.bk:
        fa._BLOCK_K = args.bk

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    rng = np.random.default_rng(0)
    shape = (args.b, args.s, args.h, args.d)
    q, k, v = (
        jnp.asarray(rng.normal(size=shape, scale=0.5).astype(np.float32), dtype)
        for _ in range(3)
    )
    causal = bool(args.causal)
    if args.impl == "vanilla":
        from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
            vanilla_attention as attn,
        )
    else:
        attn = flash_attention

    fwd = jax.jit(lambda q, k, v: attn(q, k, v, causal=causal))

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    fwdbwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    t_fwd = bench(fwd, (q, k, v), iters=args.iters)
    t_bwd = bench(lambda *a: fwdbwd(*a)[0], (q, k, v), iters=args.iters)

    f_fwd, f_tot = attn_flops(args.b, args.s, args.h, args.d, causal)
    print(
        f"shape B={args.b} S={args.s} H={args.h} D={args.d} causal={causal} dtype={args.dtype}"
    )
    print(f"fwd      {t_fwd*1e3:8.2f} ms   {f_fwd/t_fwd/1e12:6.2f} TFLOP/s (real work)")
    print(f"fwd+bwd  {t_bwd*1e3:8.2f} ms   {f_tot/t_bwd/1e12:6.2f} TFLOP/s (real work)")


if __name__ == "__main__":
    main()
