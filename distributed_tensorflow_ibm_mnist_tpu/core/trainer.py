"""High-level Trainer: config -> data -> compiled epoch loop -> metrics.

This is the replacement for the reference's ``main()`` +
``MonitoredTrainingSession`` orchestration (SURVEY.md §3.1): build the model
and optimizer from a ``RunConfig``, place the dataset on device (sharded over
the ``data`` mesh axis when ``dp > 1``), and drive the compiled epoch runner,
emitting the BASELINE.json:2 metrics of record (images/sec/chip and
wall-clock-to-target-accuracy).
"""

from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.core.optim import make_optimizer
from distributed_tensorflow_ibm_mnist_tpu.core.state import TrainState
from distributed_tensorflow_ibm_mnist_tpu.core.steps import make_epoch_runner, make_eval_fn
from distributed_tensorflow_ibm_mnist_tpu.data import load_dataset
from distributed_tensorflow_ibm_mnist_tpu.models import get_model, model_accepts, model_default
from distributed_tensorflow_ibm_mnist_tpu.parallel.data_parallel import (
    make_dp_epoch_runner,
    replicate,
    shard_dataset,
)
from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import make_mesh
from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import enable_compile_cache
from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig
from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter


_SP_IMPLS = ("ring", "ulysses")


def _unknown_sp_impl_msg(sp_impl: str) -> str:
    return f"unknown sp_impl {sp_impl!r}; use 'ring' or 'ulysses'"


class Trainer:
    """Owns the compiled functions + train state for one run."""

    def __init__(self, config: RunConfig, mesh=None, writer: MetricWriter | None = None,
                 chaos=None, tracer=None, telemetry=None):
        self.config = config
        # utils/chaos.FaultInjector | None — every chaos site below guards
        # with `is not None`, so an unwired trainer runs zero chaos
        # instructions on its hot paths (asserted by scripts/chaos_soak.py)
        self._chaos = chaos
        # utils/tracing.Tracer | None — same nil-guard contract as chaos:
        # per-epoch dispatch/fetch spans, per-chunk H2D/dispatch spans in
        # stream mode, checkpoint/restore events (docs/OBSERVABILITY.md)
        self._tracer = tracer
        # utils/telemetry.Telemetry | None — same nil-guard contract.
        # fit() stamps a heartbeat + step gauge at each fetch interval and
        # lets the sampler snapshot trainer vitals alongside the serving
        # tier's (one shared Telemetry gives one cluster time-series)
        self._telemetry = telemetry
        self._tel_epochs = 0
        self._tel_step: int | None = None
        if telemetry is not None:
            telemetry.register_source("trainer", self._telemetry_vitals)
        # compile accounting is always on (process-global listener, zero
        # cost between compiles): fit() reports the programs IT compiled
        from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

        self._compile = CompileTracker.install()
        if tracer is not None:
            self._compile.bind(tracer)
        # the trainer OWNS the writer only when it built one itself — a
        # caller-supplied writer (harnesses sharing one log) must
        # survive this trainer's close()
        self._owns_writer = writer is None
        self.writer = writer or MetricWriter(path=config.metrics_path, stdout=not config.quiet)
        enable_compile_cache()

        data = load_dataset(
            config.dataset, n_train=config.n_train, n_test=config.n_test,
            seed=config.seed, synthetic=config.synthetic, **config.dataset_kwargs,
        )
        self.num_classes = data["num_classes"]
        # which source synthetic=None actually resolved to (provenance)
        self.data_synthetic: bool = bool(data.get("synthetic", True))

        self.tp = max(1, config.tp)
        self.sp = max(1, config.sp)
        self.pp = max(1, config.pp)
        self.dp = config.dp if config.dp else max(
            1, len(jax.devices()) // (self.tp * self.sp * self.pp)
        )
        if config.dcn_dp < 1:
            raise ValueError(f"dcn_dp must be >= 1, got {config.dcn_dp}")
        if config.dcn_dp > 1 and mesh is not None:
            raise ValueError(
                "dcn_dp with an explicit mesh is ambiguous — build the "
                "multislice mesh yourself via make_mesh(..., dcn_dp=N) and "
                "leave config.dcn_dp at 1, or pass no mesh"
            )
        # dcn_dp > 1 forces the mesh build so its multislice validation
        # runs (a dp=1 run would otherwise silently ignore the request)
        if mesh is None and (self.dp > 1 or self.tp > 1 or self.sp > 1
                             or self.pp > 1 or config.dcn_dp > 1):
            mesh = make_mesh(dp=self.dp, tp=self.tp, sp=self.sp, pp=self.pp,
                             dcn_dp=config.dcn_dp)
        self.mesh = mesh
        if config.fsdp and self.dp <= 1:
            raise ValueError(
                "fsdp=True needs dp>1 (ZeRO-3 shards over the 'data' axis); "
                f"got dp={self.dp}"
            )
        if self.pp > 1 and (self.sp > 1 or config.fsdp):
            raise ValueError(
                "pp composes with dp (batch over 'data') and with tp on the "
                "NON-pipelined leaves only (embed/head/patch — stacked-block "
                "leaves are claimed by the 'pipe' sharding; TP inside stages "
                "would need explicit-collective blocks, a measured rejection "
                "— see README); sp (nested shard_map islands) and fsdp do "
                "not pipeline yet"
            )
        # pp x tp INSIDE stages (round 4, closing VERDICT.md r3 item 9):
        # the GPipe island runs explicit-collective Megatron stage blocks
        # (parallel/pipeline.make_tp_block_stage_fn) when the stack is MHA.
        # The GQA q_proj/kv_proj layout has its own split; that composition
        # keeps the honest round-2 narrowing (warned below).
        # heads_kv resolves through the family default like heads (r4
        # advisor: kwargs-only lookup would mis-route a family that
        # DEFAULTED heads_kv < heads onto the MHA island), and the island
        # is claimed only when heads/dim resolve to positive values — a
        # pp-capable family without them falls to the warned
        # pipe-only-sharding path instead of a ZeroDivisionError in
        # _make_pipeline_fn's dim // heads.  GQA stacks run the island
        # too (round 5) when tp divides heads_kv — shard s then owns q
        # heads [s*heads/tp, ...) and kv heads [s*heads_kv/tp, ...), and
        # every q head's group lands in its own shard's kv block; an
        # unaligned heads_kv keeps the honest warning below.
        mk_hkv = int(config.model_kwargs.get(
            "heads_kv", model_default(config.model, "heads_kv", 0) or 0) or 0)
        mk_heads = int(config.model_kwargs.get(
            "heads", model_default(config.model, "heads", 0) or 0))
        mk_dim = int(config.model_kwargs.get(
            "dim", model_default(config.model, "dim", 0) or 0))
        hkv_aligned = mk_hkv in (0, mk_heads) or (
            mk_hkv % self.tp == 0 and mk_heads % mk_hkv == 0
        )
        self._pp_tp_in_stages = (
            self.pp > 1 and self.tp > 1 and hkv_aligned
            and mk_heads > 0 and mk_dim > 0
        )
        if self._pp_tp_in_stages and mk_heads % self.tp:
            raise ValueError(
                f"pp x tp inside stages needs heads ({mk_heads}) divisible "
                f"by tp ({self.tp})"
            )
        if self.pp > 1 and self.tp > 1 and not self._pp_tp_in_stages:
            # honest-composition notice (VERDICT.md r2 item 8), now scoped
            # to stacks whose head counts don't align with tp.
            import warnings

            warnings.warn(
                f"pp={self.pp} x tp={self.tp} with heads_kv={mk_hkv}: "
                "stacked-block params are sharded over 'pipe' only; "
                "Megatron 'model' sharding applies to the non-pipelined "
                "leaves (embeddings/head/patch). Attention/MLP weights "
                "inside stages are NOT tensor-parallel (MHA stacks are "
                "since round 4, GQA stacks with tp | heads_kv since "
                "round 5).",
                stacklevel=2,
            )
        # MoE + dp>1 runs expert-parallel automatically: experts sharded over
        # 'data', tokens exchanged by all_to_all (VERDICT.md round-1 item 2).
        self._moe_ep = (
            self.dp > 1
            and bool(config.model_kwargs.get("moe_every", 0))
            and model_accepts(config.model, "moe_fn")
        )
        # FSDP/TP/SP/PP/EP all run under the same GSPMD epoch runner; only
        # the param spec tree differs (fsdp shards over 'data', tp over
        # 'model', pp over 'pipe', experts over 'data').
        self._gspmd = (
            self.tp > 1 or self.sp > 1 or self.pp > 1 or config.fsdp or self._moe_ep
        )
        # ZeRO-1 sharded weight update (PAPERS.md: cross-replica weight-update
        # sharding).  Two forms: the explicit bucketed shard_map step on the
        # plain-dp paths (self._dp_sharded, a collectives.ShardedUpdate), and
        # an opt-state spec upgrade on the fsdp GSPMD path (self._opt_specs).
        self._dp_sharded = None
        self._opt_specs = None
        if config.sharded_update:
            if self.dp <= 1:
                raise ValueError(
                    "sharded_update shards the weight update over the 'data' "
                    f"axis; needs dp>1, got dp={self.dp}"
                )
            if config.sharded_update_buckets < 1:
                raise ValueError(
                    f"sharded_update_buckets must be >= 1, got "
                    f"{config.sharded_update_buckets}"
                )
            if self._gspmd and not config.fsdp:
                raise ValueError(
                    "sharded_update composes with plain dp (bucketed "
                    "reduce-scatter step) and with fsdp (opt-spec upgrade); "
                    "tp/sp/pp/expert runs already shard their updates via "
                    "GSPMD param specs"
                )

        n_train = data["train_images"].shape[0]
        self.steps_per_epoch = n_train // config.batch_size
        if self.steps_per_epoch == 0:
            raise ValueError(
                f"batch_size {config.batch_size} exceeds training-set size {n_train}"
            )
        total_steps = self.steps_per_epoch * config.epochs

        model_kwargs = dict(config.model_kwargs)
        if self.dp > 1 and not self._gspmd and model_accepts(config.model, "axis_name"):
            # cross-replica BatchNorm: global-batch moments via pmean over ICI.
            # (GSPMD paths — tp/sp/fsdp — have no named axis, and BN moments
            # are already semantically global there.)
            model_kwargs.setdefault("axis_name", "data")
        # The attention path's effective causal flag: an explicit
        # model_kwargs["causal"] wins, else an explicit (non-None)
        # config.causal, else the model FAMILY's declared default
        # (causal_lm ships causal=True).  Derived here — not read raw off
        # the config — so RunConfig(model="causal_lm", sp=4) can never
        # silently train a bidirectional "causal" LM (VERDICT.md r2 item
        # 3), and the tri-state default means RunConfig(causal=False) is
        # a REAL bidirectional opt-out rather than indistinguishable from
        # unset (r3 advisor).
        self.causal = bool(
            model_kwargs["causal"]
            if "causal" in model_kwargs
            else (
                config.causal if config.causal is not None
                else model_default(config.model, "causal", False)
            )
        )
        # Analytic attention-FLOPs inputs for attn='flash' runs: the Pallas
        # custom call reports no FLOPs to XLA cost analysis, so _epoch_flops
        # supplements it with utils/flops.attention_flops (VERDICT.md r2
        # item 2).  Captured here while model_kwargs still holds the user's
        # architecture choices.
        self._attn_flops_meta = None
        if model_kwargs.get("attn") == "flash":
            s = self._hot_seq_len(model_kwargs, data)
            heads = int(model_kwargs.get(
                "heads", model_default(config.model, "heads", 0) or 0))
            dim = int(model_kwargs.get(
                "dim", model_default(config.model, "dim", 0) or 0))
            depth = int(model_kwargs.get(
                "depth", model_default(config.model, "depth", 0) or 0))
            if s and heads and dim and depth:
                self._attn_flops_meta = {
                    "seq": s, "heads": heads, "head_dim": dim // heads,
                    "depth": depth,
                    "window": int(model_kwargs.get("window", 0) or 0),
                }
        # Families with their own causal knob (causal_lm) build their own
        # attn_fn from it: the derived flag must land in their kwargs, or
        # an explicit config.causal=False would never reach the model's
        # attention on the non-sp path (tri-state contract above).
        if (config.causal is not None and "causal" not in model_kwargs
                and model_accepts(config.model, "causal")):
            model_kwargs["causal"] = self.causal
        if self.sp > 1:
            # sequence parallelism: shard the model's attention over 'seq'
            # (SURVEY.md §5 long-context row); strategy picked by sp_impl
            if not model_accepts(config.model, "attn_fn"):
                raise ValueError(
                    f"sp={self.sp} needs a sequence model taking attn_fn "
                    f"(e.g. 'vit'); got {config.model!r}"
                )
            self._validate_sp_hot_path(model_kwargs, data)
            model_kwargs.setdefault("attn_fn", self._make_sp_attn(model_kwargs))
        elif (self.causal and model_accepts(config.model, "attn_fn")
              and not model_accepts(config.model, "causal")):
            # causal without sp, for families with no causal knob of their
            # own (ViT): inject the masked single-device kernel.  Families
            # that DO accept `causal` (causal_lm) build their own attn_fn —
            # with their full option set (window, ...) — so injecting here
            # would silently drop those options.
            from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
                vanilla_attention,
            )

            if model_kwargs.get("attn") == "flash":
                from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import (
                    flash_attention,
                )

                model_kwargs.setdefault(
                    "attn_fn", functools.partial(flash_attention, causal=True)
                )
            else:
                model_kwargs.setdefault(
                    "attn_fn", functools.partial(vanilla_attention, causal=True)
                )
        if self.pp > 1:
            if not model_accepts(config.model, "pipeline_fn"):
                raise ValueError(
                    f"pp={self.pp} needs a model with a pipelineable block "
                    f"stack (pipeline_fn/pp_stages, e.g. 'vit'); got {config.model!r}"
                )
            model_kwargs.setdefault("pp_stages", self.pp)
            model_kwargs.setdefault("pipeline_fn", self._make_pipeline_fn())
        if self._moe_ep:
            n_exp = model_kwargs.get("n_experts", 8)
            if n_exp % self.dp:
                raise ValueError(
                    f"expert parallelism needs n_experts ({n_exp}) divisible "
                    f"by dp ({self.dp})"
                )
            from distributed_tensorflow_ibm_mnist_tpu.parallel.expert_parallel import (
                make_moe_dispatch_auto,
            )

            model_kwargs.setdefault("moe_fn", make_moe_dispatch_auto(
                self.mesh, n_exp,
                capacity_factor=model_kwargs.get("moe_capacity_factor", 2.0),
                top_k=int(model_kwargs.get("moe_top_k", 1)),
            ))
        if config.remat == "blocks":
            if not model_accepts(config.model, "block_remat"):
                raise ValueError(
                    f"remat='blocks' needs a block-structured model "
                    f"(resnet*/vit); got {config.model!r}"
                )
            model_kwargs.setdefault("block_remat", True)
        self.model = get_model(
            config.model, num_classes=self.num_classes, **model_kwargs
        )
        if config.sharded_update and not self._gspmd:
            # the clip link is lifted OUT of the chain: the sharded step
            # applies it against the true cross-shard norm (optim.py)
            from distributed_tensorflow_ibm_mnist_tpu.core.optim import (
                make_sharded_update_optimizer,
            )

            self.tx, sharded_clip = make_sharded_update_optimizer(config, total_steps)
        else:
            self.tx = make_optimizer(config, total_steps)

        root = jax.random.PRNGKey(config.seed)
        state_rng, self._data_rng = jax.random.split(root)
        sample = jnp.zeros((1,) + data["train_images"].shape[1:], jnp.uint8)
        if config.sharded_update and not self._gspmd:
            from distributed_tensorflow_ibm_mnist_tpu.core.optim import (
                init_sharded_opt_state,
            )
            from distributed_tensorflow_ibm_mnist_tpu.parallel.collectives import (
                ShardedUpdate,
                make_bucket_layout,
            )

            def _sharded_opt_init(params):
                # layout derives from the real param tree, so build it here
                # (inside create) and let the state initialize directly in
                # bucket form — no replicated tree is ever materialized
                layout = make_bucket_layout(
                    params, self.dp, n_buckets=config.sharded_update_buckets
                )
                self._dp_sharded = ShardedUpdate(layout=layout, clip=sharded_clip)
                return init_sharded_opt_state(self.tx, params, layout)

            state = TrainState.create(
                self.model, self.tx, state_rng, sample, opt_init=_sharded_opt_init
            )
        else:
            state = TrainState.create(self.model, self.tx, state_rng, sample)

        if config.input_mode not in ("device", "stream"):
            raise ValueError(f"input_mode must be 'device' or 'stream', got {config.input_mode!r}")
        self._stream = config.input_mode == "stream"
        if self._stream and self._gspmd:
            raise ValueError(
                "input_mode='stream' does not compose with tp/sp/pp/fsdp/"
                "expert parallelism; use device mode"
            )
        # Compile-census path label: every parallelism knob that changes
        # WHICH programs fit() compiles gets a token, so by-site compile
        # attribution distinguishes e.g. train_epoch[dp4_fsdp] from
        # train_epoch[dp4] and the census (tests/test_train_census.py)
        # can pin per-path program counts.
        _parts = [f"dp{self.dp}"]
        if config.fsdp:
            _parts.append("fsdp")
        if self.tp > 1:
            _parts.append(f"tp{self.tp}")
        if self.sp > 1:
            _parts.append(f"sp{self.sp}")
        if self.pp > 1:
            _parts.append(f"pp{self.pp}")
        if config.sharded_update:
            _parts.append("su")
        if self._stream:
            _parts.append("stream")
        self._path_label = "_".join(_parts)
        if self.pp > 1:
            m = config.pp_microbatches or self.pp
            if config.batch_size % (self.dp * m):
                raise ValueError(
                    f"batch_size {config.batch_size} must be a multiple of "
                    f"dp*microbatches ({self.dp}x{m}={self.dp * m}) so training "
                    f"always uses the pipeline island"
                )
        step_kw = dict(
            label_smoothing=config.label_smoothing, fused_xent=config.fused_xent,
            remat=config.remat is True, grad_accum=config.grad_accum,
        )
        if self._stream:
            # host-resident dataset (HBM holds only the in-flight batches);
            # batches are assembled by the C++ prefetcher (data/native.py,
            # numpy fallback) and fed to a per-step compiled train step
            self.train_images = np.ascontiguousarray(data["train_images"])
            self.train_labels = np.ascontiguousarray(data["train_labels"], np.int32)
            if self.dp > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from distributed_tensorflow_ibm_mnist_tpu.parallel.data_parallel import (
                    AXIS,
                    make_dp_chunk_runner,
                    make_dp_train_step,
                )

                img_ndim = self.train_images.ndim
                self._train_step = make_dp_train_step(
                    self.model, self.tx, self.mesh, img_ndim=img_ndim,
                    sharded_update=self._dp_sharded, state=state, **step_kw
                )
                self._train_chunk = make_dp_chunk_runner(
                    self.model, self.tx, self.mesh, img_ndim=img_ndim,
                    sharded_update=self._dp_sharded, state=state, **step_kw
                )
                # H2D placement for _run_epoch_stream: device_put against
                # the step/chunk runners' in_specs (batch split over 'data',
                # chunk axis replicated) so host batches land PRE-SHARDED
                # instead of default-device-placed and re-laid-out
                tail = [None] * (img_ndim - 1)
                self._step_shardings = {
                    "image": NamedSharding(self.mesh, P(AXIS, *tail)),
                    "label": NamedSharding(self.mesh, P(AXIS)),
                }
                self._chunk_shardings = {
                    "image": NamedSharding(self.mesh, P(None, AXIS, *tail)),
                    "label": NamedSharding(self.mesh, P(None, AXIS)),
                }
            else:
                from distributed_tensorflow_ibm_mnist_tpu.core.steps import (
                    make_chunk_runner,
                    make_train_step,
                )

                self._train_step = jax.jit(
                    make_train_step(self.model, self.tx, **step_kw), donate_argnums=(0,)
                )
                self._train_chunk = jax.jit(
                    make_chunk_runner(self.model, self.tx, **step_kw), donate_argnums=(0,)
                )
                # dp=1: plain device_put (single device, no layout to pin)
                self._step_shardings = None
                self._chunk_shardings = None
        elif self._gspmd:
            # DP x TP (x SP) under GSPMD: Megatron specs on dense stacks
            # (replicated when tp=1), ring-attention islands when sp>1, dataset
            # sharded over 'data', the whole epoch one jitted scan — same
            # shape as the other paths, only shardings differ.
            from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
                chain_rules,
                make_param_specs,
                make_tp_epoch_runner,
                megatron_rule,
            )

            if config.fsdp:
                # ZeRO-3: params + opt state sharded over 'data'; with tp>1
                # the Megatron dims are kept and FSDP shards the remainder
                from distributed_tensorflow_ibm_mnist_tpu.parallel.fsdp import make_fsdp_specs

                self._tp_specs = make_fsdp_specs(
                    state.params, self.mesh,
                    base_rule=megatron_rule(self.tp) if self.tp > 1 else None,
                )
                if config.sharded_update:
                    # ZeRO-1 residue on ZeRO-3: moments of min_size-replicated
                    # params shard over 'data' too (fsdp.make_fsdp_opt_specs)
                    from distributed_tensorflow_ibm_mnist_tpu.parallel.fsdp import (
                        make_fsdp_opt_specs,
                    )

                    self._opt_specs = make_fsdp_opt_specs(
                        state, self.mesh, self._tp_specs
                    )
            else:
                # structural rules (stacked pipe stages, expert dims) first:
                # the Megatron name rules must not see those leaves
                rules = []
                if self.pp > 1:
                    from distributed_tensorflow_ibm_mnist_tpu.parallel.pipeline import (
                        pipeline_block_rule,
                    )

                    rules.append(pipeline_block_rule())
                if self._moe_ep:
                    from distributed_tensorflow_ibm_mnist_tpu.parallel.expert_parallel import (
                        moe_expert_rule,
                    )

                    rules.append(moe_expert_rule())
                rules.append(megatron_rule(self.tp))
                self._tp_specs = make_param_specs(state.params, chain_rules(*rules))
            self._run_epoch = make_tp_epoch_runner(
                self.model, self.tx, self.mesh, self._tp_specs, state,
                config.batch_size, img_ndim=data["train_images"].ndim,
                opt_specs=self._opt_specs, **step_kw,
            )
            self.train_images, self.train_labels = shard_dataset(
                self.mesh, data["train_images"], data["train_labels"]
            )
        elif self.dp > 1:
            self.train_images, self.train_labels = shard_dataset(
                self.mesh, data["train_images"], data["train_labels"]
            )
            self._run_epoch = make_dp_epoch_runner(
                self.model, self.tx, config.batch_size, self.mesh,
                img_ndim=self.train_images.ndim,
                sharded_update=self._dp_sharded, state=state, **step_kw,
            )
        else:
            self.train_images = jax.device_put(data["train_images"])
            self.train_labels = jax.device_put(data["train_labels"])
            self._run_epoch = jax.jit(
                make_epoch_runner(self.model, self.tx, config.batch_size, **step_kw),
                donate_argnums=(0,),
            )

        if self.mesh is not None:
            # parallel eval: test set sharded over 'data', each scanned batch
            # constrained to that axis — eval uses every chip of the run's own
            # mesh (chief-only eval idled dp-1 of them; VERDICT.md item 3)
            from distributed_tensorflow_ibm_mnist_tpu.parallel.data_parallel import (
                shard_eval_set,
            )

            self.test_images, self.test_labels, n_test_valid = shard_eval_set(
                self.mesh, data["test_images"], data["test_labels"]
            )
            self._eval = jax.jit(make_eval_fn(
                self.model, config.eval_batch_size, n_valid=n_test_valid, mesh=self.mesh,
            ))
        else:
            self.test_images = jax.device_put(data["test_images"])
            self.test_labels = jax.device_put(data["test_labels"])
            self._eval = jax.jit(make_eval_fn(self.model, config.eval_batch_size))
        self.state = self._place_state(state)
        self.history: list[dict[str, Any]] = []

        self._ckpt = None
        if config.checkpoint_dir:
            from distributed_tensorflow_ibm_mnist_tpu.utils.checkpoint import CheckpointManager

            self._ckpt = CheckpointManager(config.checkpoint_dir, chaos=chaos)

    def _telemetry_vitals(self) -> dict:
        """Health-sampler source (utils/telemetry): training progress as
        O(1) host reads — no device sync, safe every sampling interval."""
        return {
            "epochs_done": self._tel_epochs,
            "weight_step": self._tel_step,
            "history_len": len(self.history),
        }

    def _make_pipeline_fn(self):
        """The pp>1 block-stack hook: GPipe island when the batch divides
        (dp x microbatches), local stage scan otherwise (init samples, eval
        remainders — GSPMD gathers the pipe-sharded params there, which only
        non-hot-path shapes ever pay).

        With ``tp > 1`` (and an MHA block stack) the island runs the
        EXPLICIT-collective Megatron stage blocks
        (parallel/pipeline.make_tp_block_stage_fn): attention and MLP
        weights sharded over ``model`` INSIDE stages via per-leaf island
        specs, one psum per sublayer pair — closing the round-2/3
        "pp x tp shards only non-block leaves" narrowing (VERDICT.md r3
        item 9).  The fallback path still runs the flax stack on the
        SAME stored params, which is what pins the two numerically.
        """
        import jax as _jax

        from distributed_tensorflow_ibm_mnist_tpu.parallel.pipeline import (
            make_pipeline_apply,
        )

        mesh, dp, m = self.mesh, self.dp, (self.config.pp_microbatches or self.pp)
        tp_stage_fn = tp_specs_fn = tp_permute = None
        if self.tp > 1 and self._pp_tp_in_stages:
            from distributed_tensorflow_ibm_mnist_tpu.parallel.pipeline import (
                make_tp_block_stage_fn,
                permute_kv_shard_major,
                permute_qkv_head_major,
                tp_stage_specs,
            )

            mk = self.config.model_kwargs
            heads = int(mk.get("heads", model_default(self.config.model, "heads", 0)))
            dim = int(mk.get("dim", model_default(self.config.model, "dim", 0)))
            head_dim = dim // heads
            hkv = int(mk.get(
                "heads_kv",
                model_default(self.config.model, "heads_kv", 0) or 0) or 0)
            if hkv == heads:
                hkv = 0  # full-width kv: the model builds the fused qkv stack
            window = int(mk.get("window", 0) or 0)
            rope = (
                model_accepts(self.config.model, "pos")
                and mk.get("pos", model_default(self.config.model, "pos", "")) == "rope"
            )
            if mk.get("attn") == "flash":
                from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import (
                    flash_attention,
                )

                attn = functools.partial(
                    flash_attention, causal=self.causal, window=window)
            else:
                from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
                    vanilla_attention,
                )

                attn = functools.partial(
                    vanilla_attention, causal=self.causal, window=window)
            tp_stage_fn = make_tp_block_stage_fn(
                heads, head_dim, self.tp, attn, rope=rope,
                dtype=mk.get("dtype", jnp.bfloat16),
                block_remat=self.config.remat == "blocks",
                heads_kv=hkv,
            )
            tp_specs_fn = tp_stage_specs
            tp_permute = (
                functools.partial(permute_kv_shard_major, heads_kv=hkv,
                                  head_dim=head_dim, tp=self.tp)
                if hkv else
                functools.partial(
                    permute_qkv_head_major, heads=heads, head_dim=head_dim)
            )

        def pipeline_fn(stage_fn, stacked_params, x):
            if x.shape[0] % (dp * m) == 0:
                if tp_stage_fn is not None:
                    tp_stacked = tp_permute(stacked_params)
                    island = make_pipeline_apply(
                        tp_stage_fn, mesh, n_microbatches=m, batch_axis="data",
                        param_specs=tp_specs_fn(tp_stacked),
                    )
                    return island(tp_stacked, x)
                island = make_pipeline_apply(
                    stage_fn, mesh, n_microbatches=m, batch_axis="data",
                )
                return island(stacked_params, x)

            def body(c, ps):
                return stage_fn(ps, c), None

            out, _ = _jax.lax.scan(body, x, stacked_params)
            return out

        return pipeline_fn

    def _hot_seq_len(self, model_kwargs: dict, data: dict) -> int | None:
        """Sequence length the attention island sees on the TRAINING path:
        the token length for rank-2 (LM) data, the patch-grid size for image
        data through a patchifying model; None when unknown."""
        shape = data["train_images"].shape
        if len(shape) == 2:
            return int(shape[1])
        if model_accepts(self.config.model, "patch_size") and len(shape) == 4:
            p = int(model_kwargs.get(
                "patch_size", model_default(self.config.model, "patch_size", 1)
            ))
            return (shape[1] // p) * (shape[2] // p)
        return None

    def _validate_sp_hot_path(self, model_kwargs: dict, data: dict) -> None:
        """Refuse configs whose TRAINING batches would silently miss the sp
        island (VERDICT.md r2 item 3).  The islands fall back to local
        full-sequence attention for non-dividing shapes — correct and wanted
        for init samples and eval remainders, but a config whose every hot
        batch falls back is an O(S^2)-memory run wearing an sp badge."""
        cfg = self.config
        if cfg.sp_impl not in _SP_IMPLS:
            raise ValueError(_unknown_sp_impl_msg(cfg.sp_impl))
        ga = max(1, cfg.grad_accum)
        if cfg.batch_size % ga:
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by "
                f"grad_accum={ga} (the per-step microbatch is batch/accum)"
            )
        if (cfg.batch_size // ga) % self.dp:
            raise ValueError(
                f"sp={self.sp}: per-step microbatch (batch_size "
                f"{cfg.batch_size} / grad_accum {ga} = {cfg.batch_size // ga}) "
                f"must divide by dp={self.dp}, or every training step would "
                "fall back to unsharded attention"
            )
        if model_kwargs.get("window", 0) and cfg.sp_impl == "ring":
            raise ValueError(
                f"sp={self.sp} with window={model_kwargs['window']}: the ring "
                "rotates K/V shards and cannot window-limit its hops — use "
                "sp_impl='ulysses' (full sequence local after the head "
                "reshard, window passes through) or sp=1"
            )
        s = self._hot_seq_len(model_kwargs, data)
        if s is not None and s % self.sp:
            raise ValueError(
                f"sp={self.sp} does not divide the training sequence length "
                f"{s}; every training step would fall back to unsharded "
                "attention (pad the dataset's seq_len or change sp)"
            )
        if cfg.sp_impl == "ulysses":
            heads = int(model_kwargs.get(
                "heads", model_default(cfg.model, "heads", 0)
            ))
            heads_kv = int(model_kwargs.get(
                "heads_kv", model_default(cfg.model, "heads_kv", 0) or 0
            )) or heads
            if heads % self.sp or heads_kv % self.sp:
                raise ValueError(
                    f"sp_impl='ulysses' re-shards heads over the seq axis and "
                    f"needs heads % sp == 0 (and heads_kv % sp == 0 for GQA); "
                    f"got heads={heads}, heads_kv={heads_kv}, sp={self.sp} "
                    "— every training step would fall back to unsharded "
                    "attention (use sp_impl='ring' or adjust heads)"
                )

    def _make_sp_attn(self, model_kwargs: dict):
        """The sp>1 attention island per config: ring or Ulysses, with the
        DERIVED causal flag (self.causal — model-family default folded in,
        VERDICT.md r2 item 3) plumbed through."""
        cfg = self.config
        if cfg.sp_impl == "ring":
            from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
                make_ring_attention,
            )

            # attn='flash' upgrades the per-block computation to the Pallas
            # kernel (O(S_local) memory; lse-merged across ring hops)
            inner = "flash" if model_kwargs.get("attn") == "flash" else "dense"
            return make_ring_attention(self.mesh, causal=self.causal, inner=inner)
        if cfg.sp_impl == "ulysses":
            from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
                vanilla_attention,
            )
            from distributed_tensorflow_ibm_mnist_tpu.parallel.sequence_parallel import (
                make_ulysses_attention,
            )

            inner = vanilla_attention
            if model_kwargs.get("attn") == "flash":
                from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import (
                    flash_attention,
                )

                inner = flash_attention
            return make_ulysses_attention(
                self.mesh, causal=self.causal, inner_attn=inner,
                window=int(model_kwargs.get("window", 0) or 0),
            )
        raise ValueError(_unknown_sp_impl_msg(cfg.sp_impl))  # direct-call guard;
        #   the Trainer path rejects unknown impls in _validate_sp_hot_path

    def _device_snapshot(self, state: TrainState) -> TrainState:
        """Device-side deep copy of the train state, shardings preserved —
        the donation-safe backup ``measure_throughput`` takes before letting
        the epoch runner donate the live buffers.  The round-2 form was
        ``jax.device_get(self.state)``, a full params+opt-state host gather
        over PCIe that also drops the shardings (VERDICT.md r2 item 6);
        this jitted identity copy never leaves HBM.

        The copy must also keep the state's COMMITMENT: jit keys its
        executables on which inputs are committed, and ``out_shardings``
        commits every output — so pinning them on an unsharded (uncommitted)
        single-chip state made the next ``fit()`` recompile the whole epoch
        program (20.7 s inside a time-to-accuracy run on the v5e, PR
        21).  Without a mesh the copy simply follows its input.
        """
        def copy(s):
            return jax.tree.map(jnp.copy, s)

        if self.mesh is None:
            return jax.jit(copy)(state)
        shardings = jax.tree.map(lambda x: x.sharding, state)
        return jax.jit(copy, out_shardings=shardings)(state)

    def _place_state(self, state: TrainState) -> TrainState:
        """Place a host/unplaced TrainState per this trainer's layout — the
        ONE spot encoding shard-vs-replicate-vs-local, used at build and at
        every checkpoint restore (so the two can't drift)."""
        if self._gspmd:
            from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
                shard_train_state,
            )

            return shard_train_state(
                self.mesh, state, self._tp_specs, opt_specs=self._opt_specs
            )
        if self.dp > 1:
            if self._dp_sharded is not None:
                from distributed_tensorflow_ibm_mnist_tpu.parallel.data_parallel import (
                    place_sharded_update_state,
                )

                return place_sharded_update_state(
                    self.mesh, state, self._dp_sharded.layout
                )
            return replicate(self.mesh, state)
        return jax.device_put(state)

    def save_checkpoint(self, wait: bool = True) -> int | None:
        if self._ckpt is None:
            return None
        span = (self._tracer.begin("checkpoint_save", cat="train", wait=wait)
                if self._tracer is not None else None)
        try:
            return self._save_checkpoint_inner(wait)
        finally:
            if span is not None:
                # wait=False: the span covers dispatching the async save,
                # not its landing — the integrity manifest records that
                self._tracer.end(span)

    def _save_checkpoint_inner(self, wait: bool) -> int | None:
        state = self.state
        if self._dp_sharded is not None:
            # gather-on-save for the ZeRO-1 buckets: the on-disk opt arrays
            # are whole (one contiguous bucket each) instead of dp scattered
            # shard files — inspectable offline, and restore still lands
            # directly in the sharded layout (the restore target's shardings
            # steer orbax, see restore_checkpoint).  Bucket padding is a
            # function of dp, so cross-dp resume remains config-bound either
            # way; params/stats stay as placed (already replicated).
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            state = state.replace(opt_state=jax.tree.map(
                lambda x: jax.device_put(x, rep) if isinstance(x, jax.Array) else x,
                state.opt_state,
            ))
        return self._ckpt.save(state, wait=wait)

    def restore_checkpoint(self, step: int | None = None) -> int:
        """Resume from the checkpoint dir; returns the restored step.

        With ``step=None`` the restore is the HARDENED form
        (``CheckpointManager.restore_latest_intact``): torn/corrupt/
        non-finite steps are walked past, newest → oldest, instead of
        crashing the resume — a crash mid-save costs at most the epochs
        since the previous durable step.  An explicit ``step`` restores
        exactly that step (and raises on corruption), for forensics.
        """
        if self._ckpt is None:
            raise ValueError("no checkpoint_dir configured")
        # the live state is the restore target: its shardings steer orbax to
        # load each leaf directly into this run's layout (no host staging);
        # _place_state is then a no-op re-assert of the placement contract
        span = (self._tracer.begin("checkpoint_restore", cat="train",
                                   hardened=step is None)
                if self._tracer is not None else None)
        try:
            if step is None:
                restored = self._ckpt.restore_latest_intact(self.state)
            else:
                restored = self._ckpt.restore(self.state, step=step)
        except Exception as e:
            if span is not None:
                # the checkpoint-integrity failure event: the hardened walk
                # exhausted every step, or the explicit step was corrupt
                self._tracer.end(span, error=f"{type(e).__name__}: {e}")
            raise
        self.state = self._place_state(restored)
        self._gen_params = None  # decode-params cache keyed off the old state
        step_restored = int(jax.device_get(self.state.step))
        if span is not None:
            self._tracer.end(span, restored_step=step_restored)
        return step_restored

    def _run_epoch_stream(self, state, epoch_rng, preemption=None):
        """One epoch in stream mode: C++-prefetched host batches -> compiled
        steps.  Batches are shipped in chunks of ``stream_chunk`` — ONE
        host->device transfer per chunk, then a compiled scan over its steps —
        so the fixed per-transfer and per-dispatch latency is amortized
        ``stream_chunk``-fold.  Transfers go through
        ``jax.device_put`` against the dp batch sharding (bare
        ``jnp.asarray`` paid default-device placement plus a relayout under
        dp>1) and are DOUBLE-BUFFERED one chunk ahead: chunk i+1's H2D is
        dispatched before chunk i's compute is awaited, so the transfer
        this path is bound by (PERFORMANCE.md §Input modes: ~13k img/s
        H2D-bound) overlaps the scan instead of serializing with it.
        Metrics stay device-side until epoch end so the dispatch pipeline
        never blocks on a host readback.

        ``preemption`` with ``config.preempt_poll_every > 0`` is polled at
        step granularity (every poll boundary the computed-step counter
        crosses): a SIGTERM mid-epoch stops the epoch at the next boundary
        with the steps run so far, so the grace window is spent
        checkpointing, not finishing an epoch that may not fit in it
        (fit() sees ``triggered`` at the epoch boundary and does the
        checkpoint-and-exit).  Unrun prefetched batches — including a
        staged-but-uncomputed chunk — are dropped; the resumed run replays
        them (state.step records exactly what ran).
        """
        from distributed_tensorflow_ibm_mnist_tpu.data.native import Prefetcher

        cfg = self.config
        n = self.train_images.shape[0]
        seed = int(jax.device_get(jax.random.randint(epoch_rng, (), 0, 2**31 - 1)))
        perm = np.random.default_rng(seed).permutation(n)[
            : self.steps_per_epoch * cfg.batch_size
        ].astype(np.int32)
        chunk = max(1, cfg.stream_chunk)
        poll = max(0, cfg.preempt_poll_every)
        ms = []
        pending_imgs: list[np.ndarray] = []
        pending_labs: list[np.ndarray] = []
        steps_done = 0
        next_poll = poll
        staged = None  # device-resident chunk whose compute hasn't run yet

        tracer = self._tracer  # nil-guarded in the closures below

        def stage():
            # ship ONE assembled chunk host->device, pre-sharded; the
            # transfer is async under JAX's dispatch, which is what the
            # one-chunk-ahead staging exploits
            batch = {
                "image": np.stack(pending_imgs),
                "label": np.stack(pending_labs),
            }
            pending_imgs.clear()
            pending_labs.clear()
            span = (tracer.begin("h2d", cat="train", steps=chunk)
                    if tracer is not None else None)
            # innermost site wins: transfer-program compiles land on the
            # h2d site, not the enclosing train_epoch site
            with self._compile.site(f"h2d[{self._path_label}]"):
                if self._chunk_shardings is not None:
                    out = jax.device_put(batch, self._chunk_shardings)
                else:
                    out = jax.device_put(batch)
            if span is not None:
                tracer.end(span)  # enqueue time; the transfer itself is async
            return out

        def run_chunk(state, batches):
            nonlocal steps_done
            span = (tracer.begin("dispatch", cat="train", steps=chunk)
                    if tracer is not None else None)
            try:
                state, m = self._train_chunk(state, batches)  # scan, k steps
            finally:
                if span is not None:
                    tracer.end(span)
            ms.append(m)
            steps_done += chunk
            return state

        def run_step(state, img, lab):
            nonlocal steps_done
            batch = {"image": img, "label": lab}
            span = (tracer.begin("h2d", cat="train", steps=1)
                    if tracer is not None else None)
            with self._compile.site(f"h2d[{self._path_label}]"):
                if self._step_shardings is not None:
                    batch = jax.device_put(batch, self._step_shardings)
                else:
                    batch = jax.device_put(batch)
            if span is not None:
                tracer.end(span)
                span = tracer.begin("dispatch", cat="train", steps=1)
            try:
                state, m = self._train_step(state, batch)
            finally:
                if span is not None:
                    tracer.end(span)
            ms.append(m)
            steps_done += 1
            return state

        stopped = False
        with Prefetcher(
            self.train_images, self.train_labels, cfg.batch_size, perm,
            depth=cfg.prefetch_depth,
        ) as pf:
            for img, lab in pf:
                if self._chaos is not None:
                    self._chaos.raise_if_fired("data-batch", OSError)
                if chunk == 1:
                    state = run_step(state, img, lab)
                else:
                    pending_imgs.append(img)
                    pending_labs.append(lab)
                    if len(pending_imgs) == chunk:
                        # double buffer: dispatch chunk i+1's H2D, THEN run
                        # chunk i's compute — the new transfer overlaps it
                        nxt = stage()
                        if staged is not None:
                            state = run_chunk(state, staged)
                        staged = nxt
                if poll and preemption is not None and steps_done >= next_poll:
                    next_poll = steps_done + poll
                    if preemption.triggered:
                        stopped = True
                        break
        if not stopped:
            if staged is not None:
                state = run_chunk(state, staged)
                staged = None
            # epoch-end remainder (< chunk): drain through the per-step
            # program instead of compiling a second k-step scan shape
            for img, lab in zip(pending_imgs, pending_labs):
                state = run_step(state, img, lab)
            pending_imgs.clear()
            pending_labs.clear()
        # per-chunk metrics are (k,)-stacked; per-step ones are scalars
        flat = {
            k: jnp.concatenate([jnp.atleast_1d(m[k]) for m in ms]) for k in ms[0]
        }
        return state, flat

    @property
    def n_chips(self) -> int:
        """Devices the run occupies: the images/sec/chip denominator."""
        return max(1, self.dp) * max(1, self.tp) * max(1, self.sp) * max(1, self.pp)

    def _tokens_per_sec(self, sequences_per_sec: float) -> float | None:
        """sequences/sec -> tokens/sec for token-sequence data (rank-2
        inputs, i.e. the LM datasets); None for image data."""
        if self.train_images.ndim != 2:
            return None
        return round(sequences_per_sec * self.train_images.shape[1], 1)

    def _epoch_flops(self) -> float | None:
        """Per-device FLOPs of one compiled epoch (XLA cost analysis of the
        post-partitioning module; None in stream mode / off-table backends).

        XLA's cost analysis counts a while-loop BODY once regardless of trip
        count (verified on both the TPU and CPU backends with a scanned
        matmul), so the reported figure is scaled by the epoch scan's step
        count and the nested grad-accum scan's microbatch count.  Loops whose
        bodies are not the FLOPs carrier (the epoch permutation, ring/pipeline
        inner loops at their single-chip trip counts) make this accurate for
        the zoo's standard paths, with one documented edge: a slight
        undercount under sp/pp islands.  With ``grad_accum > 1`` the uniform
        x(steps x accum) scaling would also multiply the ops OUTSIDE the
        microbatch scan — the optimizer update, which runs once per step,
        not once per microbatch — so its separately-measured FLOPs are
        subtracted back out (accum-1) times per step (round-5 verdict
        item 7; previously a documented slight overcount).
        """
        if self._stream:
            return None
        from distributed_tensorflow_ibm_mnist_tpu.utils.flops import compiled_flops

        per_call = compiled_flops(
            self._run_epoch, self.state, self.train_images, self.train_labels,
            jax.random.PRNGKey(0),
        )
        if per_call is None:
            return None
        accum = max(1, self.config.grad_accum)
        per_epoch = per_call * self.steps_per_epoch * accum
        if accum > 1:
            opt = self._opt_update_flops()
            if opt:
                per_epoch -= opt * self.steps_per_epoch * (accum - 1)
        return per_epoch + self._flash_attn_flops_per_epoch()

    def _opt_update_flops(self) -> float | None:
        """FLOPs of ONE optimizer update (tx.update + apply_updates), from
        cost analysis of the update jitted alone — the correction term for
        ``grad_accum`` runs, where the epoch scaling would otherwise count
        it once per microbatch.  Measured unsharded; under dp>1 the real
        per-device update is smaller or equal, so the subtraction never
        over-corrects by more than the (elementwise-sized) term itself.
        Memoized: the param/opt-state structure is fixed for a trainer,
        and the lower+compile behind cost analysis is seconds at scale
        (code-review r5).
        """
        cached = getattr(self, "_opt_flops_cache", None)
        if cached is not None:
            return cached[0]
        if self._dp_sharded is not None:
            # bucketed opt state is not a params-shaped tree; skipping the
            # correction keeps the documented slight overcount for the
            # (sharded_update x grad_accum>1) corner instead of crashing
            return None
        import optax

        from distributed_tensorflow_ibm_mnist_tpu.utils.flops import compiled_flops

        def update(grads, opt_state, params):
            updates, new_state = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_state

        flops = compiled_flops(
            jax.jit(update), self.state.params, self.state.opt_state,
            self.state.params,
        )
        self._opt_flops_cache = (flops,)
        return flops

    def _flash_attn_flops_per_epoch(self) -> float:
        """Per-device analytic attention FLOPs per epoch for attn='flash'
        runs (utils/flops.attention_flops; 0 otherwise).

        Mosaic only: under the Pallas interpreter (ops/interpret.py) the
        kernels lower to ordinary HLO that cost analysis already counts —
        adding the analytic figure there would double-book.  The per-device
        divisor is dp*sp*pp: dp shards the batch, ring/Ulysses shard the
        attention S^2 work over 'seq', pp divides the depth; tp does NOT
        divide it (the custom call runs with the full head set per device).
        """
        from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import interpret_forced
        from distributed_tensorflow_ibm_mnist_tpu.utils.flops import attention_flops

        meta = self._attn_flops_meta
        if not meta or interpret_forced():
            return 0.0

        per_step = attention_flops(
            self.config.batch_size, meta["seq"], meta["heads"],
            meta["head_dim"], causal=self.causal, with_backward=True,
            depth=meta["depth"], window=meta.get("window", 0),
        )
        return per_step * self.steps_per_epoch / (self.dp * self.sp * self.pp)

    def measure_throughput(self, epochs: int = 10) -> dict[str, Any]:
        """Steady-state training throughput + MFU under the run's own layout
        — the supported benchmark API (VERDICT.md round-1 item 9).

        Dispatches ``epochs`` chained epoch programs back-to-back with ONE
        readback at the end: per-epoch blocking readbacks measure the
        host<->device link, not the chip (the epoch-scale analog of the
        reference's per-step feed_dict sync, SURVEY.md §3.1): a blocking
        readback drains the dispatch pipeline, so the chip idles while the
        host turns around.  The first epoch runs outside
        the timed region to absorb XLA compile; the trainer's state is
        snapshotted first and restored after, so training is undisturbed.
        """
        if self._stream:
            raise ValueError("measure_throughput requires input_mode='device'")
        if epochs < 1:
            raise ValueError(f"epochs must be >= 1, got {epochs}")
        import math

        cfg = self.config
        state0 = self._device_snapshot(self.state)  # epoch runner donates its input
        rng = jax.random.PRNGKey(123)
        try:
            t0 = time.perf_counter()
            state, m = self._run_epoch(
                self.state, self.train_images, self.train_labels, rng
            )
            jax.device_get(m["loss"])  # readback: the execution fence
            compile_and_first_epoch_s = time.perf_counter() - t0

            t1 = time.perf_counter()
            for i in range(epochs):
                state, m = self._run_epoch(
                    state, self.train_images, self.train_labels, jax.random.fold_in(rng, i)
                )
            last_loss = float(np.mean(jax.device_get(m["loss"])))
            wall = time.perf_counter() - t1
            if not math.isfinite(last_loss):
                raise RuntimeError(
                    f"non-finite loss during throughput measurement: {last_loss}"
                )

            images = self.steps_per_epoch * cfg.batch_size * epochs
            ips_chip = images / wall / self.n_chips
            flops_epoch = self._epoch_flops()
            from distributed_tensorflow_ibm_mnist_tpu.utils.flops import mfu as _mfu

            fps_chip = flops_epoch * epochs / wall if flops_epoch else None
            result = {
                "images_per_sec": round(images / wall, 1),
                "images_per_sec_per_chip": round(ips_chip, 1),
                "epochs": epochs,
                "steps_per_epoch": self.steps_per_epoch,
                "batch_size": cfg.batch_size,
                "chips": self.n_chips,
                "compile_and_first_epoch_s": round(compile_and_first_epoch_s, 3),
                "model_tflops_per_sec_per_chip": (
                    round(fps_chip / 1e12, 6) if fps_chip else None
                ),
                "mfu": (lambda v: round(v, 6) if v is not None else None)(_mfu(fps_chip)),
                "last_loss": last_loss,
                "device": str(jax.devices()[0]),
            }
            tokens = self._tokens_per_sec(ips_chip)
            if tokens is not None:
                result["tokens_per_sec_per_chip"] = tokens
            return result
        finally:
            # the warm call donated self.state's buffers — restore even on
            # error so the trainer honors "training is undisturbed".  The
            # snapshot is already placed in this run's exact layout, so a
            # plain assignment restores it with zero transfers.
            self.state = state0

    def _decode_params(self):
        """The run's params re-laid-out for single-device decode, cached.

        The re-layout is ``jax.device_put`` to a single-device sharding —
        a compiled device-to-device reshard (ICI gather on TPU), so for
        tp/fsdp-sharded runs the params NEVER visit the host (the round-2
        ``measure_throughput`` lesson — see ``_device_snapshot`` — applied
        to inference: the round-3 form ``device_put(device_get(params))``
        hauled every weight over PCIe to the host and back per call).
        Invalidated by
        identity whenever training replaces ``self.state``.

        Stored in the model's COMPUTE dtype (round 5): decode never
        updates params, so the f32 master copy has no business in the
        serving loop — the cast halves the decode copy's HBM residency
        (a whole spare parameter set at serving scale) and removes the
        once-per-call cast XLA otherwise hoists out of the decode loop
        (docs/PERFORMANCE.md measures the in-loop bytes identical either
        way).  Only leaves flax itself casts per use are converted —
        Dense/Embed/Conv weights, ~99% of the bytes — so the cast
        commutes exactly (f32→bf16 is the same single rounding up front
        or per use).  LayerNorm scale/bias (``norm_*`` modules) and MoE
        expert/router leaves (``moe``) stay f32: flax's ``_normalize``
        and this repo's expert einsums consume them at f32 precision, so
        pre-rounding THOSE would change decode logits vs the on_mesh
        path's masters (code-review r5).  Integer leaves pass through.
        """
        src = self.state.params
        cached = getattr(self, "_gen_params", None)
        if cached is not None and cached[0] is src:
            return cached[1]
        tree = self._decode_param_tree()
        dtype = self.config.model_kwargs.get(
            "dtype", model_default(self.config.model, "dtype", jnp.bfloat16))

        def cast(path, leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            names = tuple(str(getattr(k, "key", k)) for k in path)
            if any(n == "moe" or n.startswith("norm") for n in names):
                return leaf  # consumed at param dtype — casting would drift
            return leaf.astype(dtype)

        tree = jax.tree_util.tree_map_with_path(cast, tree)
        dev = (
            next(iter(self.mesh.devices.flat)) if self.mesh is not None
            else jax.devices()[0]
        )
        sharding = jax.sharding.SingleDeviceSharding(dev)
        placed = jax.device_put(tree, jax.tree.map(lambda _: sharding, tree))
        self._gen_params = (src, placed)
        return placed

    def _decode_param_tree(self):
        """The run's params in the DECODE model's layout.

        Pipeline-trained runs store the block stack as one
        ``pipe_blocks/stacked`` tree with leading ``(n_stages, per_stage)``
        dims; the decode model runs the plain ``block_{i}`` stack, so the
        stacked leaves are sliced back out in schedule order
        (``block_{s*per_stage + p}`` — exactly the order the GPipe scan
        visits them, so decode logits match the trained forward).  A
        device-side slice per block; everything else passes through by
        name.
        """
        src = self.state.params
        if "pipe_blocks" not in src:
            return src
        stacked = src["pipe_blocks"]["stacked"]
        lead = jax.tree.leaves(stacked)[0].shape
        n_stages, per_stage = int(lead[0]), int(lead[1])
        out = {k: v for k, v in src.items() if k != "pipe_blocks"}
        for s_i in range(n_stages):
            for p_i in range(per_stage):
                out[f"block_{s_i * per_stage + p_i}"] = jax.tree.map(
                    lambda a: a[s_i, p_i], stacked)
        return out

    def generate(self, prompt, max_new: int, max_len: int | None = None,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 rng=None, eos_id: int | None = None, pad_id: int = 0,
                 prompt_lens=None, on_mesh: bool = False,
                 with_lengths: bool = False):
        """Autoregressive decode from this run's trained weights
        (core/generate.py; causal-LM family only).

        Device-resident and reusable: params are re-laid-out on device
        once per trained state (no host round-trip — ``_decode_params``)
        and the compiled generator is cached per (max_len, max_new,
        sampling) configuration, so repeated calls with the same prompt
        shape re-jit nothing.  Pass ``max_len`` explicitly to share one
        compiled cache size across varying prompt lengths.  ``eos_id`` /
        ``pad_id`` / ``prompt_lens`` per :func:`~..core.generate.
        make_generator` (stop tokens, ragged right-padded prompts).
        ``make_generator``'s ``unroll`` knob is deliberately NOT plumbed
        through this API (or its cache key): it was measured a rejection
        on the v5e (see the in-body note there) — call ``make_generator``
        directly to exercise it on other hardware.
        ``with_lengths=True`` changes the return to ``(tokens,
        gen_lens)`` — ``gen_lens`` (B,) int32 is each row's REAL
        generated token count (EOS included; ``max_new`` for rows that
        never stopped), the reliable recovery handle when ``pad_id`` is
        also a legitimate vocab token.

        ``on_mesh=True`` decodes IN the run's own sharded layout instead
        of re-laying out to one device: the generator jit receives the
        tp/fsdp/EP-sharded params as-is and GSPMD partitions the decode —
        qkv/head matmuls split over ``model`` (the KV cache follows the
        activations' head sharding), fsdp layers gathered per use, and
        expert-parallel runs (round 5) keep each expert's weights on the
        shard that owns them: the clean decode model's batched expert
        einsums carry the expert-sharded ``w1/w2`` leaves, so GSPMD
        shards them over the expert axis and reduces the combine — the
        experts are never gathered to one device, which matters exactly
        when "the experts don't fit one chip" is WHY the run is EP.  This
        is the multi-chip serving form: nothing is re-laid out, nothing
        crosses the host, and a pod-sized model that cannot fit one chip
        decodes where it trained.  Requires a GSPMD run (tp/fsdp/EP);
        sp-island runs decode via the default single-device path (the
        decode model drops the training islands).
        """
        if not model_accepts(self.config.model, "pos"):
            raise ValueError(
                f"generate() needs a causal-LM-family model; got "
                f"{self.config.model!r}"
            )
        from distributed_tensorflow_ibm_mnist_tpu.core.generate import make_generator

        # pp-trained runs decode too (round 4): _decode_param_tree slices
        # the pipe_blocks/stacked tree back into the plain block_{i}
        # layout the decode model runs — but not in the pipe-sharded
        # layout itself (the stacked params have no meaning to the clean
        # decode program), so on_mesh is refused below.
        if not self.causal:
            raise ValueError(
                "generate() is autoregressive (KV-cache causal decode); this "
                "run trained a BIDIRECTIONAL model (causal=False), whose "
                "logits condition on future positions the decode path cannot "
                "provide — train causally to decode"
            )
        if on_mesh and not (self.tp > 1 or self.config.fsdp or self._moe_ep):
            # tp/fsdp/EP — NOT the rest of _gspmd: sp runs shard via
            # islands the decode model drops (their param layouts have no
            # meaning to the clean decode program), and dp-replicated runs
            # gain nothing over the default path
            raise ValueError(
                "on_mesh=True decodes in the run's GSPMD layout; this run "
                "has none (tp/fsdp/EP shard params — dp/sp and single-chip "
                "runs decode via the default path)"
            )
        if on_mesh and self.sp > 1:
            raise ValueError(
                "on_mesh=True with sp>1 is unsupported: the decode model "
                "drops the sequence-parallel islands, so its params/cache "
                "have no 'seq' layout to decode in — use the default "
                "single-device path"
            )
        if on_mesh and (self.pp > 1 or self.config.model_kwargs.get("pp_stages", 0)):
            raise ValueError(
                "on_mesh=True with pipeline stages is unsupported: the "
                "decode model runs the plain block stack, not the "
                "pipe-sharded pipe_blocks/stacked layout — use the default "
                "path (which unstacks the stages on device)"
            )
        prompt = jnp.asarray(prompt)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        if max_len is None:
            max_len = int(prompt.shape[1]) + max_new
        key = (max_len, max_new, temperature, top_k, top_p, eos_id, pad_id,
               with_lengths)
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        gen = cache.get(key)
        if gen is None:
            # a clean single-device model: the trainer's own instance may
            # carry sp/pp/moe islands (shard_map over the training mesh)
            # that have no business in the decode path; params transfer by
            # name
            clean_kwargs = {
                k: v for k, v in self.config.model_kwargs.items()
                if k not in ("attn_fn", "moe_fn", "pipeline_fn", "pp_stages")
            }
            model = get_model(self.config.model, num_classes=self.num_classes,
                              **clean_kwargs)
            gen = make_generator(model, max_len, max_new, temperature,
                                 top_k, top_p, eos_id=eos_id, pad_id=pad_id,
                                 with_lengths=with_lengths)
            cache[key] = gen
        params = self.state.params if on_mesh else self._decode_params()
        return gen(params, prompt, rng=rng, prompt_lens=prompt_lens)

    def close(self) -> None:
        """Release the trainer's metric writer (file handle + TensorBoard).

        Only closes a writer the trainer built itself; caller-supplied
        writers are the caller's to close.  Idempotent."""
        if self._owns_writer:
            self.writer.close()

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> bool:
        # MetricWriter's own context-manager contract, delegated: the
        # metrics file handle is released even when fit() raises mid-run
        self.close()
        return False

    def evaluate(self) -> dict[str, float]:
        out = jax.device_get(self._eval(self.state, self.test_images, self.test_labels))
        return {k: float(v) for k, v in out.items()}

    def fit(self, preemption=None) -> dict[str, Any]:
        """Run the configured number of epochs (early-stop on target acc).

        ``preemption``: an object with a ``triggered`` property (see
        utils/elastic.PreemptionHandler) polled between epochs — when set,
        the loop checkpoints and returns cleanly with ``preempted: True``.
        """
        cfg = self.config
        if cfg.epochs < 1:
            raise ValueError(f"epochs must be >= 1, got {cfg.epochs}")
        # training replaces the params the decode cache re-laid out: free
        # the stale single-device copy NOW rather than pinning a whole
        # extra parameter set in HBM until the next generate() call
        self._gen_params = None
        if cfg.resume and self._ckpt is not None and self._ckpt.latest_step() is not None:
            step = self.restore_checkpoint()
            self.writer.write("resume", step=step)
        chips = self.n_chips
        # Step base for metric records: nonzero after a checkpoint resume
        # (the epoch counter restarts at 0 but state.step does not).
        step0 = int(jax.device_get(self.state.step))
        t0 = time.perf_counter()
        compile0 = self._compile.snapshot()  # fit's own program family
        epoch_times: list[float] = []
        time_to_target = None
        best_acc = 0.0
        preempted = False

        # Epoch metrics stay on device between eval boundaries and are
        # fetched in ONE transfer per interval: a per-epoch blocking readback
        # would serialize the dispatch pipeline on host<->device latency (the
        # epoch-granular analog of the reference's per-step feed_dict sync,
        # SURVEY.md §3.1): the chip would idle while the host turns around.
        pending: list[tuple[int, Any]] = []
        interval_t0 = t0
        first_interval_len = 0  # epochs amortizing the XLA compile (see summary)

        # RunConfig.profile_dir: capture the steady-state epochs (VERDICT.md
        # r2 item 4).  The capture starts after the first epoch's fence so
        # the one-time XLA compile doesn't bury the steady-state timeline
        # (with epochs == 1 the compile is unavoidably in-trace).
        prof = None
        if cfg.profile_dir:
            from distributed_tensorflow_ibm_mnist_tpu.utils.profiling import TraceSession

            prof = TraceSession(cfg.profile_dir)
            if cfg.epochs == 1:
                prof.start()

        # Data-order schedule is keyed by the ABSOLUTE epoch index (epochs
        # already durable in the restored step + the local epoch counter):
        # a resumed run replays exactly the schedule the uninterrupted run
        # would have had, which is what makes recovery bit-identical
        # (scripts/chaos_soak.py asserts this end to end).  Fresh runs have
        # abs_epoch0 == 0 — nothing changes for them.
        abs_epoch0 = step0 // self.steps_per_epoch
        try:
            for epoch in range(cfg.epochs):
                epoch_rng = jax.random.fold_in(self._data_rng, abs_epoch0 + epoch)
                if self._chaos is not None:
                    spec = self._chaos.fire("train-step")
                    if spec is not None:
                        if spec.kind == "nan":
                            # poison ONE param element: the epoch's loss goes
                            # non-finite and the real divergence detector +
                            # restore path below must recover it
                            from distributed_tensorflow_ibm_mnist_tpu.utils.debug import (
                                inject_nan,
                            )

                            path, _ = jax.tree_util.tree_flatten_with_path(
                                self.state.params)[0][0]
                            leaf = "/".join(
                                str(getattr(k, "key", getattr(k, "name", k)))
                                for k in path)
                            self.state = self.state.replace(
                                params=inject_nan(self.state.params, leaf))
                        else:
                            from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
                                ChaosFault,
                            )

                            raise ChaosFault(
                                "train-step", spec.kind,
                                self._chaos.events("train-step") - 1)
                espan = (self._tracer.begin("epoch_dispatch", cat="train",
                                            epoch=epoch)
                         if self._tracer is not None else None)
                try:
                    with self._compile.site(f"train_epoch[{self._path_label}]"):
                        if self._stream:
                            self.state, metrics = self._run_epoch_stream(
                                self.state, epoch_rng, preemption=preemption)
                        else:
                            # async dispatch: this span measures enqueue, not
                            # compute — the interval's "fetch" span below is
                            # where the device time surfaces (the fence)
                            self.state, metrics = self._run_epoch(
                                self.state, self.train_images,
                                self.train_labels, epoch_rng)
                except BaseException as e:
                    # a faulted epoch still closes its span — the timeline
                    # shows WHERE the run died, and run_with_recovery's
                    # restart instant lands on a leak-free tracer
                    if espan is not None:
                        self._tracer.end(espan, error=type(e).__name__)
                    raise
                if espan is not None:
                    self._tracer.end(espan)
                pending.append((epoch, metrics))
                if prof is not None and not prof.active:
                    # fence epoch 0 (compile + run) out, then trace the rest;
                    # the extra readback is the documented profiling cost
                    jax.device_get(metrics["loss"])
                    prof.start()
                eval_now = (epoch + 1) % cfg.eval_every == 0 or epoch == cfg.epochs - 1
                preempt_now = preemption is not None and preemption.triggered
                ckpt_now = (
                    self._ckpt is not None
                    and cfg.checkpoint_every
                    and (epoch + 1) % cfg.checkpoint_every == 0
                )
                if not (eval_now or preempt_now or ckpt_now):
                    continue  # keep the device queue full; no host sync this epoch

                fspan = (self._tracer.begin("fetch", cat="train",
                                            interval_epochs=len(pending))
                         if self._tracer is not None else None)
                fetched = jax.device_get([m for _, m in pending])
                if fspan is not None:
                    # the fence: every dispatched epoch in the interval
                    # completed inside this span
                    self._tracer.end(fspan)
                interval = time.perf_counter() - interval_t0
                epoch_time = interval / len(pending)  # amortized over the interval
                if first_interval_len == 0:
                    first_interval_len = len(pending)
                images = self.steps_per_epoch * cfg.batch_size
                for (ep, _), mh in zip(pending, fetched):
                    mh = {k: float(np.mean(v)) for k, v in mh.items()}
                    if not np.isfinite(mh["loss"]):
                        # divergence detection (SURVEY.md §5 sanitizer analog):
                        # fail loudly, with the offending leaves localized, after
                        # letting any in-flight async checkpoint land
                        # (run_with_recovery will reopen this directory)
                        from distributed_tensorflow_ibm_mnist_tpu.utils.debug import (
                            TrainingDiverged,
                            find_nonfinite,
                        )

                        if self._ckpt is not None:
                            self._ckpt.wait()
                        # bad_leaves are localized from the CURRENT state — with
                        # eval_every > 1 that is up to eval_every-1 epochs past
                        # the diverged one (metrics are fetched per interval);
                        # set eval_every=1 to localize at the diverged epoch.
                        raise TrainingDiverged(
                            f"non-finite train loss in epoch {ep} "
                            f"(leaves localized from end-of-interval state, "
                            f"epoch {epoch})",
                            step=step0 + self.steps_per_epoch * (ep + 1),
                            bad_leaves=find_nonfinite(self.state.params),
                        )
                    epoch_times.append(epoch_time)
                    record = {
                        "epoch": ep,
                        "train_loss": mh["loss"],
                        "train_accuracy": mh["accuracy"],
                        # timing is amortized over the fetch interval (one host
                        # readback per interval; the first interval also folds in
                        # the XLA compile) — interval_epochs flags that so JSONL
                        # consumers don't read these as true per-epoch timings
                        "epoch_time_s": round(epoch_time, 4),
                        "interval_epochs": len(pending),
                        "images_per_sec": round(images / epoch_time, 1),
                        "images_per_sec_per_chip": round(images / epoch_time / chips, 1),
                    }
                    if "moe_dropped_frac" in mh:
                        # routing observability (VERDICT.md r3 item 5): the
                        # epoch-mean fraction of (token, choice) assignments
                        # dropped at expert capacity — nonzero means
                        # capacity_factor is undersized for this run
                        record["moe_dropped_frac"] = round(
                            mh["moe_dropped_frac"], 6)
                    if ep == epoch and eval_now:
                        vspan = (self._tracer.begin("eval", cat="train",
                                                    epoch=ep)
                                 if self._tracer is not None else None)
                        with self._compile.site(f"eval[{self._path_label}]"):
                            ev = self.evaluate()
                        if vspan is not None:
                            self._tracer.end(vspan)
                        record["test_accuracy"] = ev["accuracy"]
                        record["test_loss"] = ev["loss"]
                        best_acc = max(best_acc, ev["accuracy"])
                        if (
                            time_to_target is None
                            and cfg.target_accuracy
                            and ev["accuracy"] >= cfg.target_accuracy
                        ):
                            time_to_target = time.perf_counter() - t0
                    self.history.append(record)
                    self.writer.write("epoch", step=step0 + self.steps_per_epoch * (ep + 1), **record)
                    if self._telemetry is not None:
                        self._tel_epochs += 1
                        self._tel_step = step0 + self.steps_per_epoch * (ep + 1)
                        self._telemetry.heartbeat("trainer")
                        self._telemetry.set_gauge("trainer_step",
                                                  self._tel_step)
                        self._telemetry.maybe_sample()
                pending.clear()
                if ckpt_now:
                    self.save_checkpoint(wait=False)
                if time_to_target is not None and cfg.target_accuracy:
                    break
                if preempt_now:
                    preempted = True
                    self.save_checkpoint(wait=True)
                    self.writer.write("preempted", step=int(jax.device_get(self.state.step)))
                    break
                interval_t0 = time.perf_counter()
        finally:
            if prof is not None:
                prof.stop()

        total_time = time.perf_counter() - t0
        # The first fetch interval includes XLA compile (amortized over its
        # epochs); the steady-state rate excludes that whole interval, and the
        # compile overhead is the first interval's excess over steady pace.
        steady = epoch_times[first_interval_len:] or epoch_times
        steady_mean = sum(steady) / len(steady) if steady else 0.0
        compile_overhead = (
            max(0.0, (epoch_times[0] - steady_mean) * first_interval_len)
            if epoch_times
            else 0.0
        )
        images = self.steps_per_epoch * cfg.batch_size
        summary = {
            "name": cfg.name,
            "epochs_run": len(epoch_times),
            "total_time_s": round(total_time, 3),
            "compile_overhead_s": round(compile_overhead, 3),
            "best_test_accuracy": best_acc,
            "time_to_target_s": round(time_to_target, 3) if time_to_target else None,
            "target_accuracy": cfg.target_accuracy,
            "images_per_sec": round(images / steady_mean, 1),
            "images_per_sec_per_chip": round(images / steady_mean / chips, 1),
            # global leaf sizes: layout-independent, valid at any dp/tp/sp
            "param_count": self.state.param_count(),
            "device": str(jax.devices()[0]),
        }
        # compile accounting (ISSUE 6): programs THIS fit compiled — the
        # per-PR regression gate for the r04→r05 cold-compile watch item
        from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

        cdelta = CompileTracker.delta(self._compile.snapshot(), compile0)
        summary["n_compiled_programs"] = cdelta["n_compiled_programs"]
        summary["compile_time_s"] = round(cdelta["compile_time_s"], 3)
        # path-qualified site attribution (train_epoch[...]/eval[...]/
        # h2d[...]) — what tests/test_train_census.py pins per path;
        # strict JSON (plain dicts, ints, floats)
        summary["compile_by_site"] = cdelta["by_site"]
        tokens = self._tokens_per_sec(images / steady_mean / chips) if steady_mean else None
        if tokens is not None:
            summary["tokens_per_sec_per_chip"] = tokens
        flops_epoch = self._epoch_flops()
        if flops_epoch and steady_mean:
            from distributed_tensorflow_ibm_mnist_tpu.utils.flops import mfu as _mfu

            fps_chip = flops_epoch / steady_mean
            summary["model_tflops_per_sec_per_chip"] = round(fps_chip / 1e12, 6)
            m = _mfu(fps_chip)
            summary["mfu"] = round(m, 6) if m is not None else None
        if preempted:
            summary["preempted"] = True
            # the preemption path already saved; re-saving the same step
            # would delete-and-rewrite it during the SIGTERM grace window
        if self._ckpt is not None and not preempted:
            self.save_checkpoint(wait=True)
        self.writer.write("summary", **summary)
        return summary
