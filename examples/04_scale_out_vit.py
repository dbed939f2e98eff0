"""Compose DP x TP x SP on a ViT — the scale-out machinery.

Everything the reference could not do: Megatron-style tensor parallelism
(GSPMD PartitionSpecs over the 'model' axis), ring attention over the
'seq' axis, batch over 'data' — one jitted train step, shardings only.
Needs 8 devices; with fewer it self-arms an 8-device virtual CPU mesh,
says so on stderr if that meant leaving an accelerator, and prints the
platform it ran on:

    python examples/04_scale_out_vit.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo-root import without install

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_tensorflow_ibm_mnist_tpu.core.state import TrainState
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.parallel import make_mesh
from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import make_ring_attention
from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
    make_param_specs, make_tp_train_step, megatron_dense_rule, shard_train_state,
)

if __name__ == "__main__":
    if len(jax.devices()) < 8:
        from distributed_tensorflow_ibm_mnist_tpu.utils.hostmesh import (
            ensure_virtual_cpu_devices,
        )

        ensure_virtual_cpu_devices(8)
    mesh = make_mesh(dp=2, tp=2, sp=2)  # needs 8 devices
    vit = get_model(
        "vit", patch_size=7, dim=64, depth=4, heads=4,
        attn_fn=make_ring_attention(mesh),
    )
    tx = optax.adamw(1e-3)
    state = TrainState.create(vit, tx, jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1), jnp.uint8))
    specs = make_param_specs(state.params, megatron_dense_rule())
    step = make_tp_train_step(vit, tx, mesh, specs, state)
    state = shard_train_state(mesh, state, specs)

    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.integers(0, 255, (16, 28, 28, 1), dtype=np.uint8)),
        "label": jnp.asarray(rng.integers(0, 10, (16,)).astype(np.int32)),
    }
    for i in range(5):
        state, metrics = step(state, batch)
        print(f"step {i}: loss {float(metrics['loss']):.4f}")
    print(f"\nDP x TP x SP ViT step ran on {jax.default_backend()}:", mesh)
