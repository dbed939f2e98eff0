"""Config-driven pipeline + expert parallelism — one RunConfig field each.

Round 2 of the rebuild made every parallelism strategy config-driven: this
example trains (a) a ViT whose block stack streams through a GPipe pipeline
(`pp=4`: stage-stacked params sharded over the 'pipe' mesh axis, microbatches
hopping stages via ppermute) and (b) a Mixture-of-Experts ViT whose experts
(and their adam moments) shard over 'data' with all_to_all token dispatch —
wired automatically the moment a MoE model trains at dp>1 (Switch top-1 by
default; `model_kwargs={"moe_top_k": 2}` switches to GShard top-2 routing
with choice-priority capacity filling). Needs 8 devices;
with fewer it self-arms the 8-device virtual CPU mesh:

    python examples/07_pipeline_and_experts.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo-root import without install

import jax
import jax.numpy as jnp

if __name__ == "__main__":
    if len(jax.devices()) < 8:
        from distributed_tensorflow_ibm_mnist_tpu.utils.hostmesh import (
            ensure_virtual_cpu_devices,
        )

        ensure_virtual_cpu_devices(8)

    from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    # (a) dp=2 x pp=4: eight microbatches per step keep the bubble small
    # (idle fraction = (pp-1)/(m+pp-1) = 3/11 per stage).
    cfg_pp = RunConfig(
        name="vit_pipeline", model="vit",
        model_kwargs={"patch_size": 7, "dim": 32, "depth": 4, "heads": 2,
                      "dtype": jnp.float32},
        dataset="mnist", synthetic=True, n_train=512, n_test=128,
        batch_size=64, epochs=2, lr=1e-3, dp=2, pp=4, pp_microbatches=8,
        eval_batch_size=128, quiet=True,
    )
    t = Trainer(cfg_pp)
    stacked = t.state.params["pipe_blocks"]["stacked"]
    leaf = jax.tree.leaves(stacked)[0]
    print(f"pipeline: stacked block params {leaf.shape}, sharded {leaf.sharding.spec}")
    s = t.fit()
    print(f"pipeline fit: acc {s['best_test_accuracy']:.3f} "
          f"({s['images_per_sec']:.0f} img/s across {t.n_chips} devices)\n")

    # (b) MoE + dp=8: expert parallelism is automatic — each device OWNS
    # n_experts/dp experts; tokens route via all_to_all over 'data'.
    cfg_moe = RunConfig(
        name="vit_moe_ep", model="vit",
        model_kwargs={"patch_size": 7, "dim": 32, "depth": 2, "heads": 2,
                      "moe_every": 1, "n_experts": 8, "dtype": jnp.float32},
        dataset="mnist", synthetic=True, n_train=512, n_test=128,
        batch_size=64, epochs=2, lr=1e-3, dp=8,
        eval_batch_size=128, quiet=True,
    )
    t = Trainer(cfg_moe)
    w1 = t.state.params["block_0"]["moe"]["w1"]
    print(f"experts: w1 {w1.shape} sharded {w1.sharding.spec} "
          f"({w1.shape[0] // 8} experts owned per device)")
    s = t.fit()
    print(f"moe fit on {jax.default_backend()}: acc {s['best_test_accuracy']:.3f} "
          f"({s['images_per_sec']:.0f} img/s across {t.n_chips} devices)")
