"""ZeRO-1 sharded weight update on the data-parallel path.

The reference all-reduced gradients and then ran the SAME optimizer update
on every worker (SURVEY.md §2.4) — per-worker update FLOPs and optimizer
memory did not shrink as workers were added.  ``sharded_update=True``
applies the cross-replica weight-update sharding recipe (PAPERS.md)
instead: gradients flatten into a few contiguous buckets, each bucket
REDUCE-SCATTERS (each chip keeps its 1/N block), the optimizer updates only
that block against dp-SHARDED adam moments, and the updated param buckets
all-gather.  Same loss trajectory as the replicated update; optimizer
FLOPs and mutable optimizer memory divided by dp.

    python examples/09_sharded_update.py
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
        name=f"sharded_update_dp{n}", model="mlp", dataset="mnist",
        batch_size=64 * n, epochs=3, lr=2e-3, dp=n, sharded_update=True,
    )
    if jax.default_backend() == "cpu":
        import jax.numpy as jnp

        cfg = cfg.replace(
            model_kwargs={"hidden": (256,), "dtype": jnp.float32},
            n_train=8192, n_test=2048,
        )
    trainer = Trainer(cfg)
    summary = trainer.fit()

    # show the layout doing its job: adam moments live 1/N per chip
    layout = trainer._dp_sharded.layout
    bucket_leaves = [
        leaf for leaf in jax.tree.leaves(trainer.state.opt_state)
        if getattr(leaf, "ndim", 0) == 1 and leaf.size in set(layout.bucket_sizes)
    ]
    local = sum(next(iter(leaf.addressable_shards)).data.size for leaf in bucket_leaves)
    total = sum(leaf.size for leaf in bucket_leaves)
    print(
        f"\n{n}-way DP with sharded update on {jax.default_backend()}: "
        f"{summary['images_per_sec']:.0f} images/sec, "
        f"best acc {summary['best_test_accuracy']:.4f}\n"
        f"buckets: {layout.bucket_sizes} ({len(layout.slots)} param leaves "
        f"packed into {layout.n_buckets} reduce-scatters/step)\n"
        f"optimizer moments per chip: {local:,} of {total:,} elements "
        f"(1/{n} — the ZeRO-1 memory split)"
    )
