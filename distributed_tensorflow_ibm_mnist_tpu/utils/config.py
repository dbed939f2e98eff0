"""Run configuration + the five BASELINE.md benchmark presets.

Replaces the reference's config layer (SURVEY.md §5 "Config / flag system":
``tf.app.flags`` role flags + K8s env injection).  SPMD has no chief/ps/worker
roles, so a run is fully described by one dataclass; the BASELINE.json:6-12
configs are named presets; CLI overrides come from ``launch/cli.py``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass
class RunConfig:
    """Complete description of a training run."""

    name: str = "run"
    # model
    model: str = "lenet5"
    model_kwargs: dict[str, Any] = field(default_factory=dict)
    # data
    dataset: str = "mnist"
    dataset_kwargs: dict[str, Any] = field(default_factory=dict)  # generator
    #   extras, e.g. {"vocab": 64, "seq_len": 1024} for dataset="retrieval"
    synthetic: bool | None = None  # None = real cache if present, else synthetic
    n_train: int | None = None
    n_test: int | None = None
    # optimization
    batch_size: int = 128  # global batch
    epochs: int = 10
    optimizer: str = "adam"  # adam | sgd | momentum
    lr: float = 1e-3
    schedule: str = "constant"  # constant | cosine | warmup_cosine
    warmup_steps: int = 0
    weight_decay: float = 0.0
    momentum: float = 0.9
    grad_clip: float | None = None  # clip gradients to this global L2 norm
    #   (optax.clip_by_global_norm inside the compiled step; the norm is exact
    #   in every layout — shard_map DP clips after the pmean, GSPMD grads are
    #   logically global.  collectives.grad_norm_global remains the primitive
    #   for hand-rolled shard_map loops that clip BEFORE reduction.)
    label_smoothing: float = 0.0
    fused_xent: bool = False  # Pallas fused softmax-xent kernel (ops/xent.py) for the train loss
    grad_accum: int = 1  # microbatches per step (gradient accumulation)
    remat: bool | str = False  # False | True | "blocks".  True checkpoints the
    #   WHOLE forward (saves scan residuals across steps only — peak memory
    #   within a step is unchanged, measured on v5e).  "blocks" checkpoints
    #   each residual/transformer block (models with block_remat), the real
    #   per-step memory lever: batch-4096 ResNet-50 trains on one 16G chip
    #   with "blocks" where both False and True OOM at 19.7G.
    # input pipeline
    input_mode: str = "device"  # device: dataset HBM-resident, scan epochs;
    #                             stream: host-resident, C++-prefetched per-step batches
    prefetch_depth: int = 3  # stream mode: batches assembled ahead of the consumer
    stream_chunk: int = 8  # stream mode: batches per host->device transfer (1 = per-step);
    #                        each chunk is one compiled scan, amortizing transfer latency
    # parallelism
    dp: int = 1  # data-parallel degree; 0 => all visible devices (divided by tp*sp first)
    tp: int = 1  # tensor-parallel degree over the 'model' mesh axis (GSPMD
    #              Megatron specs on dense_{i} stacks; composes with dp)
    sp: int = 1  # sequence-parallel degree over the 'seq' mesh axis (model
    #              must accept attn_fn, e.g. 'vit')
    sp_impl: str = "ring"  # 'ring' (ppermute K/V rotation, scales past H
    #                        devices) | 'ulysses' (all_to_all head resharding;
    #                        composes with attn='flash' as the inner kernel)
    causal: bool | None = None  # causal attention mask, plumbed through
    #   whichever attn path is active (sp island or single-device).
    #   Tri-state: None (default) defers to the model FAMILY's declared
    #   default (causal_lm ships causal=True); an explicit True/False wins
    #   over the family default, so causal=False really trains a
    #   bidirectional causal_lm.  model_kwargs={"causal": ...} outranks
    #   both (it configures the model itself).
    pp: int = 1  # pipeline-parallel degree over the 'pipe' mesh axis (GPipe
    #              scan+ppermute over the ViT block stack; model must accept
    #              pipeline_fn/pp_stages and depth % pp == 0; composes with dp)
    pp_microbatches: int = 0  # microbatches streamed through the pipeline per
    #                           step; 0 = pp (one in flight per stage).  More
    #                           microbatches shrink the bubble: pp/(m+pp-1)
    #                           of ticks are idle per stage.
    fsdp: bool = False  # ZeRO-3: shard params + opt state over 'data' (needs
    #                     dp>1; composes with tp into the 2D TP-within layout)
    sharded_update: bool = False  # ZeRO-1 sharded weight update (needs dp>1).
    #   Plain-dp runs: gradients flatten into a few size-balanced contiguous
    #   buckets, each bucket reduce-scatters instead of all-reducing, the
    #   optimizer updates only this replica's 1/N block against dp-SHARDED
    #   optimizer state, and the updated param buckets all-gather — per-chip
    #   optimizer FLOPs and mutable optimizer memory drop by dp while the
    #   loss trajectory stays that of the replicated update (PAPERS.md:
    #   "Automatic Cross-Replica Sharding of Weight Update").  fsdp runs:
    #   upgrades the optimizer-state specs so even the moments of
    #   min_size-replicated params shard over 'data'.  Off by default until
    #   parity is proven on the target topology (tests pin it on the
    #   virtual mesh).
    sharded_update_buckets: int = 4  # gradient buckets for sharded_update's
    #   flatten (more buckets = finer comm/compute overlap, more collective
    #   launches; 4 is a good default for small-to-mid models)
    dcn_dp: int = 1  # multislice: how many TPU slices the data axis spans
    #   (dcn_dp must divide dp; only the gradient all-reduce crosses DCN,
    #   model/seq/pipe collectives stay on each slice's ICI — see
    #   parallel/mesh.make_mesh)
    # run control
    seed: int = 0
    target_accuracy: float | None = None  # stop early when test acc reaches this
    eval_every: int = 1  # epochs between evals
    eval_batch_size: int = 2000
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0  # epochs between saves; 0 = final save only (if dir set)
    resume: bool = False  # restore latest INTACT checkpoint from checkpoint_dir before
    #   training (torn/corrupt newest steps are walked past — utils/checkpoint.py
    #   restore_latest_intact; the resumed run replays the original data schedule)
    preempt_poll_every: int = 0  # stream mode: poll the PreemptionHandler every N
    #   steps so a SIGTERM grace window is spent checkpointing, not finishing the
    #   epoch; 0 = epoch-boundary polling only (device mode always polls at epoch
    #   boundaries — the epoch is one compiled dispatch there)
    metrics_path: str | None = None  # JSONL file (always also stdout unless quiet)
    quiet: bool = False  # suppress stdout metric lines (tests/benchmarks)
    profile_dir: str | None = None  # capture an XLA/TPU profile of the
    #   steady-state epochs of fit() into this dir (TensorBoard profile
    #   plugin format; utils/profiling).  The first epoch — XLA compile —
    #   is fenced out of the trace when epochs > 1.  CLI: --profile DIR.

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


# The five measurement configs from BASELINE.json:6-12 / BASELINE.md.
PRESETS: dict[str, RunConfig] = {
    # 1. "MNIST 2-layer MLP, single-process, batch=32 (CPU smoke test)"
    "mnist_mlp_smoke": RunConfig(
        name="mnist_mlp_smoke", model="mlp", model_kwargs={"hidden": (256,)},
        dataset="mnist", batch_size=32, epochs=3, lr=1e-3, dp=1,
        target_accuracy=0.97,
    ),
    # 2. "MNIST LeNet-5 CNN, single TPU core, batch=128"
    "mnist_lenet_1chip": RunConfig(
        name="mnist_lenet_1chip", model="lenet5", dataset="mnist",
        batch_size=128, epochs=12, lr=1e-3, schedule="cosine", dp=1,
        target_accuracy=0.99,
    ),
    # 3. "MNIST CNN, 8-core TPUStrategy-equivalent data-parallel, global batch=1024"
    "mnist_cnn_dp8": RunConfig(
        name="mnist_cnn_dp8", model="lenet5", dataset="mnist",
        batch_size=1024, epochs=20, lr=2e-3, schedule="warmup_cosine",
        warmup_steps=100, dp=8, target_accuracy=0.99,
    ),
    # 4. "Fashion-MNIST ResNet-20, v4-32 data-parallel"
    "fashion_resnet20_dp32": RunConfig(
        name="fashion_resnet20_dp32", model="resnet20", dataset="fashion_mnist",
        batch_size=4096, epochs=30, optimizer="momentum", lr=0.4,
        schedule="warmup_cosine", warmup_steps=200, weight_decay=1e-4, dp=32,
        target_accuracy=0.90,
    ),
    # 5. "CIFAR-10 ResNet-50, v4-32 (stretch beyond MNIST)"
    "cifar_resnet50_dp32": RunConfig(
        name="cifar_resnet50_dp32", model="resnet50", dataset="cifar10",
        batch_size=4096, epochs=40, optimizer="momentum", lr=0.4,
        schedule="warmup_cosine", warmup_steps=300, weight_decay=1e-4, dp=32,
        target_accuracy=0.90,
    ),
}


def get_preset(name: str) -> RunConfig:
    try:
        return PRESETS[name]
    except KeyError:
        raise ValueError(f"unknown preset {name!r}; available: {sorted(PRESETS)}") from None
