"""Data-parallel training over every visible device.

The replacement for the reference's chief/ps/worker cluster (SURVEY.md
§3.1): no roles, no ClusterSpec — one SPMD program over a named mesh,
gradients all-reduced in-graph over ICI.  Runs on any device count; with
fewer than 2 devices it self-arms an 8-device virtual CPU mesh
(laptop/CI mode), says so on stderr if that meant leaving an accelerator,
and prints the platform it ran on:

    python examples/02_data_parallel.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo-root import without install

import jax

from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
from distributed_tensorflow_ibm_mnist_tpu.utils.hostmesh import ensure_virtual_cpu_devices

if __name__ == "__main__":
    if len(jax.devices()) < 2:
        ensure_virtual_cpu_devices(8)
    n = len(jax.devices())
    cfg = RunConfig(
        name=f"lenet_dp{n}", model="lenet5", dataset="mnist",
        batch_size=128 * n, epochs=5, lr=2e-3, dp=n,  # dp=0 also means "all"
    )
    if jax.default_backend() == "cpu":
        # Keep the virtual-mesh demo fast: the N virtual devices time-share
        # the host's cores, so run the MLP on a small split instead of
        # LeNet's convs (same DP machinery, laptop-friendly wall clock).
        import jax.numpy as jnp

        cfg = cfg.replace(
            model="mlp", model_kwargs={"dtype": jnp.float32},
            n_train=8192, n_test=2048, epochs=3,
        )
    summary = Trainer(cfg).fit()
    print(f"\n{n}-way DP on {jax.default_backend()}: "
          f"{summary['images_per_sec']:.0f} images/sec total, "
          f"{summary['images_per_sec_per_chip']:.0f} per chip")
