"""Opt-in real-TPU tests (skipped when no TPU is attached).

The CPU suite exercises Pallas kernels through the interpreter on purpose
(SURVEY.md §4, ops/interpret.py); these tests compile the SAME kernels with
Mosaic on the actual chip in a subprocess running the host's own (TPU)
environment, so a kernel that only works interpreted cannot land green.
The kernel probes and the LeNet golden run are ``chip_smoke.py``'s legs —
shared, not copied.
"""

import glob
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

PROBE = "import jax; print(jax.devices()[0].platform)"


def _default_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)      # drop the CPU-mesh forcing from conftest
    env.pop("JAX_PLATFORMS", None)  # conftest pins "cpu"; let the host decide
    return env


def _tpu_plausible() -> bool:
    """Cheap signals only — the real probe (a full jax import in a
    subprocess) runs inside the test, so CPU-only collection stays free.

    Observed on the v5e host the chip tool hands out (2026-09-26, libtpu
    0.0.34): NO ``/dev/accel*``; the chip is a VFIO group, ``/dev/vfio/<n>``
    beside ``/dev/vfio/vfio``, and the environment carries
    ``TPU_ACCELERATOR_TYPE=v5litepod-4`` with ``JAX_PLATFORMS=tpu,cpu``.
    ``/dev/accel*`` stays for TPU VMs that expose the older accel driver;
    ``DTM_TPU_TESTS=1`` is the explicit switch."""
    return bool(
        glob.glob("/dev/vfio/[0-9]*")
        or glob.glob("/dev/accel*")
        or os.environ.get("TPU_ACCELERATOR_TYPE")
        or os.environ.get("DTM_TPU_TESTS")
    )


needs_tpu = pytest.mark.skipif(
    not _tpu_plausible(), reason="no TPU signals on this host")


def _run_on_tpu(worker_src: str, ok_marker: str, timeout: int = 560) -> None:
    """Probe for an attached TPU (skip if none), run ``worker_src`` in a
    default-env subprocess, and assert it printed ``ok_marker``."""
    probe = subprocess.run(
        [sys.executable, "-c", PROBE], capture_output=True, text=True,
        timeout=120, cwd=str(REPO), env=_default_env(),
    )
    if probe.returncode != 0 or not probe.stdout.strip().endswith("tpu"):
        pytest.skip(f"no TPU attached: {probe.stdout.strip()[-100:]}")
    proc = subprocess.run(
        [sys.executable, "-c", worker_src], capture_output=True, text=True,
        timeout=timeout, cwd=str(REPO), env=_default_env(),
    )
    assert proc.returncode == 0, proc.stdout[-1000:] + proc.stderr[-2000:]
    assert ok_marker in proc.stdout


def _smoke_leg(name: str) -> str:
    """A chip_smoke.py leg as a worker: it refuses a non-TPU platform,
    asserts its own checks, and prints its record."""
    return ("import sys, chip_smoke\n"
            f"rc = chip_smoke.run_leg({name!r})\n"
            "print('SMOKE_LEG_OK' if rc == 0 else 'SMOKE_LEG_FAILED', flush=True)\n"
            "sys.exit(rc)\n")


@needs_tpu
def test_pallas_kernels_on_real_tpu():
    """Every flash / fused-xent shape on the training and serving paths,
    Mosaic-compiled and within tolerance of the dense references."""
    _run_on_tpu(_smoke_leg("kernels"), "SMOKE_LEG_OK")


GSPMD = r'''
import jax, jax.numpy as jnp, numpy as np, optax
assert jax.devices()[0].platform == "tpu", jax.devices()

from distributed_tensorflow_ibm_mnist_tpu.core.state import TrainState
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import make_mesh
from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import make_ring_attention
from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
    make_param_specs, make_tp_train_step, megatron_rule, shard_train_state,
)

# The GSPMD path (jit with NamedShardings + shard_map islands) at tp=sp=1 on
# ONE chip: same program structure multi-chip runs compile, minus the ICI.
mesh = make_mesh(dp=1, tp=1, sp=1)
vit = get_model("vit", num_classes=10, patch_size=7, dim=64, depth=2, heads=4,
                attn_fn=make_ring_attention(mesh))
tx = optax.adam(1e-3)
state = TrainState.create(vit, tx, jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1), jnp.uint8))
specs = make_param_specs(state.params, megatron_rule(1))
step = make_tp_train_step(vit, tx, mesh, specs, state)
state = shard_train_state(mesh, state, specs)
rng = np.random.default_rng(0)
batch = {
    "image": jnp.asarray(rng.integers(0, 255, (64, 28, 28, 1), dtype=np.uint8)),
    "label": jnp.asarray(rng.integers(0, 10, 64).astype(np.int32)),
}
for _ in range(2):
    state, metrics = step(state, batch)
loss = float(jax.device_get(metrics["loss"]))
assert np.isfinite(loss), loss

# GPipe island on a 1-stage pipe ring: scan + ppermute + broadcast on-chip.
from distributed_tensorflow_ibm_mnist_tpu.parallel.pipeline import (
    make_pipeline_apply, stack_stage_params,
)
mesh_pp = make_mesh(dp=1, pp=1)
w = jnp.asarray(rng.normal(0, 0.3, (32, 32)).astype(np.float32))
pp_apply = jax.jit(make_pipeline_apply(
    lambda p, x: jnp.tanh(x @ p["w"]) + x, mesh_pp, n_microbatches=2,
    batch_axis="data",
))
y = pp_apply(stack_stage_params([{"w": w}]), jnp.ones((8, 32), jnp.float32))
assert np.all(np.isfinite(jax.device_get(y)))

# Flash-inner ring attention island (lse-emitting Mosaic kernel + merge +
# hand-written ring VJP) on the size-1 seq axis.
from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
    make_ring_attention, vanilla_attention,
)
mesh_sp = make_mesh(dp=1, sp=1)
qkv = [jnp.asarray(rng.normal(0, 0.5, (2, 128, 4, 64)).astype(np.float32)) for _ in range(3)]
ring_flash = make_ring_attention(mesh_sp, causal=True, inner="flash")
out_rf = jax.jit(ring_flash)(*qkv)
ref_rf = vanilla_attention(*qkv, causal=True)
assert float(jnp.max(jnp.abs(out_rf - ref_rf))) < 5e-3, "ring-flash fwd mismatch on chip"
grf = jax.jit(jax.grad(lambda q, k, v: ring_flash(q, k, v).sum(), argnums=(0, 1, 2)))(*qkv)
gref = jax.grad(lambda q, k, v: vanilla_attention(q, k, v, causal=True).sum(), argnums=(0, 1, 2))(*qkv)
for a, b in zip(grf, gref):
    assert float(jnp.max(jnp.abs(a - b))) < 5e-3, "ring-flash grad mismatch on chip"

# MoE all_to_all island on a size-1 axis.
from distributed_tensorflow_ibm_mnist_tpu.parallel.expert_parallel import make_moe_dispatch
moe = jax.jit(make_moe_dispatch(mesh_pp, n_experts=4, capacity=8))
params = {
    "router": jnp.asarray(rng.normal(0, 0.3, (32, 4)).astype(np.float32)),
    "w1": jnp.asarray(rng.normal(0, 0.3, (4, 32, 64)).astype(np.float32)),
    "b1": jnp.zeros((4, 64), jnp.float32),
    "w2": jnp.asarray(rng.normal(0, 0.3, (4, 64, 32)).astype(np.float32)),
    "b2": jnp.zeros((4, 32), jnp.float32),
}
out, aux, _ = moe(params, jnp.asarray(rng.normal(0, 1, (16, 32)).astype(np.float32)))
assert np.all(np.isfinite(jax.device_get(out))) and np.isfinite(float(aux))
print("GSPMD_TPU_OK", loss, flush=True)
'''


@needs_tpu
def test_gspmd_path_on_real_tpu():
    """VERDICT.md round-1 item 10: the GSPMD machinery every multi-chip run
    depends on (jit with NamedShardings, Megatron spec placement, ring/
    pipeline/MoE shard_map islands) compiles and executes on the real chip,
    so Mosaic/GSPMD-specific breakage can't hide behind the CPU mesh."""
    _run_on_tpu(GSPMD, "GSPMD_TPU_OK")


LM_GOLDEN = r'''
import jax, numpy as np
assert jax.devices()[0].platform == "tpu", jax.devices()
from distributed_tensorflow_ibm_mnist_tpu.core import Trainer
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
cfg = RunConfig(
    name="lm_golden", model="causal_lm",
    model_kwargs={"dim": 128, "depth": 2, "heads": 4, "attn": "flash"},
    dataset="retrieval", dataset_kwargs={"vocab": 64, "seq_len": 1024},
    n_train=2048, n_test=64, batch_size=16, epochs=7, lr=3e-3, causal=True,
    quiet=True, eval_batch_size=16, eval_every=7,
)
t = Trainer(cfg)
s = t.fit()
losses = [h["train_loss"] for h in t.history]
# uniform floor = ln(64) = 4.16; the attend-to-key head must have emerged.
# 7 epochs, not 5: emergence epoch is rounding-sensitive (the round-5
# base-2 softmax shifted it from ~5 to ~6 — measured 2.41 at 6, 1.95 at
# 7), so the budget leaves margin on both sides of the threshold.
assert losses[-1] < 2.8, losses
assert s["tokens_per_sec_per_chip"] > 50_000, s
print("LM_GOLDEN_OK", losses[-1], s["tokens_per_sec_per_chip"], flush=True)
'''


@needs_tpu
def test_causal_lm_golden_on_tpu():
    """The config-driven long-context LM (causal flash attention, 1024-token
    retrieval) learns the task on the real chip at sane token throughput."""
    _run_on_tpu(LM_GOLDEN, "LM_GOLDEN_OK")


@needs_tpu
def test_lenet_golden_metric_on_tpu():
    """SURVEY.md §4 golden-metric job: the [B:8] LeNet preset on the real
    chip, as launch/cli.py drives it, reaches its 99% threshold with finite
    loss and an MFU against the chip's own peak-table row."""
    _run_on_tpu(_smoke_leg("lenet"), "SMOKE_LEG_OK")
