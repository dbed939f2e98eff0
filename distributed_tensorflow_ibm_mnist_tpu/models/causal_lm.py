"""Decoder-only causal language model — the zoo's text/sequence family.

The reference's model layer was a single image CNN (SURVEY.md §1 L3); this
is the rebuild's language-model counterpart, promoted from the hand-rolled
examples/06 net so the long-context machinery is config-driven end to end:

    RunConfig(model="causal_lm", dataset="retrieval", causal=True,
              sp=4, sp_impl="ring", model_kwargs={"attn": "flash"})

Inputs are int token arrays (B, S); logits are per-position (B, S, vocab)
and the framework's loss/accuracy/eval paths handle the extra position axis
unchanged (per-token cross-entropy and accuracy).  Attention is causal by
default; a trainer-supplied ``attn_fn`` (the sp ring/Ulysses island) takes
priority and the Trainer DERIVES its causal flag from this family default
(``Trainer.causal``), so ``RunConfig(model="causal_lm", sp=4)`` is causal
without restating ``causal=True`` — pass ``model_kwargs={"causal": False}``
to explicitly train bidirectionally.

Positions are rotary by default (``pos="rope"``, models/transformer.py
``apply_rope``): relative-position attention with no per-position
parameters, so checkpoints don't bake in a maximum length and the model
runs on sequences longer than it trained on — the right default for the
long-context story the ring buys (VERDICT.md r2 item 5).  ``pos="learned"``
keeps the (1, S, dim) table for ablation.

Reuses :class:`~.transformer.TransformerBlock`, so TP (qkv/proj Megatron
specs), MoE blocks, and block remat all apply as they do to the ViT.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import flax.linen as nn
import jax.numpy as jnp

from distributed_tensorflow_ibm_mnist_tpu.models.transformer import TransformerBlock
from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import vanilla_attention


class CausalLM(nn.Module):
    """Embed -> pre-norm causal blocks -> per-position vocab head."""

    num_classes: int = 64  # vocabulary size (named for zoo consistency)
    dim: int = 128
    depth: int = 2
    heads: int = 4
    heads_kv: int = 0  # 0 = heads; <heads = grouped-query attention (GQA):
    #   smaller kv projections and a heads_kv-sized decode cache
    window: int = 0  # causal sliding-window attention width (0 = full
    #   context); tile-skipped in the flash kernel so cost is S*window
    mlp_ratio: int = 4
    dropout: float = 0.0
    attn_fn: Callable | None = None  # sp island (brings its OWN causal flag)
    attn: str = "vanilla"  # 'vanilla' | 'flash' for the local kernels
    causal: bool = True
    pos: str = "rope"  # 'rope' (rotary, default: length-extrapolating, no
    #   per-position params) | 'learned' (the (1, S, dim) table — bakes max
    #   length into the checkpoint; kept for ablation) | 'none'
    rope_theta: float = 10000.0  # rotary base, passed to every block's
    #   apply_rope (pos="rope")
    norm_eps: float = 1e-6  # epsilon of every LayerNorm in the stack
    sow_kv: bool = False  # sow per-block K/V on the normal forward (the
    #   flash-prefill capture; core/generate.py clones the model with this)
    kv_cache_dtype: str = "native"  # "int8": quantized decode cache with
    #   per-(position, head) scales — halves the decode's dominant HBM
    #   stream (models/transformer.quantize_kv_int8); training is untouched
    page_size: int = 0  # >0: paged decode cache — blocks read/write K/V
    #   through a shared page pool + block table (serving/kv_pool.py)
    #   instead of dense (B, max_len) rows; serving engine state, training
    #   and prefill are untouched (see TransformerBlock.page_size)
    paged_one_device: bool = False  # set by the serving engine on its
    #   decode clone when the program runs on one device (see
    #   TransformerBlock.paged_one_device): single-token paged decode may
    #   then read live pages only through the ops/paged_attention.py kernel
    tie_embeddings: bool = False  # share the token embedding with the
    #   output head (logits = x @ embed^T): V*dim fewer params, the
    #   standard small-LM regularizer.  The Megatron rule's feature-dim
    #   embedding sharding doubles as the head's row-parallel layout.
    quant: str = "none"  # "int8": WEIGHT-only int8 matmuls (ISSUE 12) —
    #   block projections and the untied logits head store int8 kernels +
    #   per-output-channel f32 scales with dequant fused into the matmul
    #   (models/quant.py).  Params must pass quantize_params_int8 (the
    #   serving engine's upload/swap seams do).  Embedding stays full
    #   precision (a gather, and the tied head shares it); orthogonal to
    #   kv_cache_dtype (weights vs decode cache).
    moe_every: int = 0
    n_experts: int = 8
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1  # experts per token: 1 = Switch, >1 = GShard top-k
    moe_z_weight: float = 0.0  # router z-loss coefficient (ST-MoE; 0 = off)
    moe_fn: Callable | None = None
    pp_stages: int = 0  # >0: stack blocks for the GPipe island (see the
    #                     ViT's StackedBlocks; params shardable over 'pipe')
    pipeline_fn: Callable | None = None  # (stage_fn, stacked_params, x) -> y
    block_remat: bool = False
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False,
                 max_len: int = 0, ragged: bool = False):
        b, s = tokens.shape
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {self.window}")
        if self.quant not in ("none", "int8"):
            raise ValueError(
                f"quant must be 'none' or 'int8', got {self.quant!r}")
        if self.quant != "none" and self.pp_stages > 0:
            raise ValueError(
                "quant composes with the plain block stack only: pp_stages "
                "stacks params (n_stages, per_stage, ...) for the training "
                "pipeline, which the int8 kernel/scale layout does not "
                "cover — decode already unstacks pp weights (core/trainer."
                "_decode_param_tree), so quantize the unstacked tree"
            )
        if decode and self.pos == "learned":
            raise ValueError(
                "decode mode needs position-free params: pos='learned' bakes "
                "the trained length into a (1, S, dim) table that cannot "
                "address incremental positions — use pos='rope' (the default)"
            )
        if decode and self.pp_stages > 0:
            raise ValueError(
                "decode mode runs the plain block stack, not stage-stacked "
                "params — Trainer.generate unstacks pp-trained weights into "
                "this layout for you (core/trainer._decode_param_tree)"
            )
        embed = nn.Embed(self.num_classes, self.dim, dtype=self.dtype,
                         name="embed")
        x = embed(tokens.astype(jnp.int32))
        if self.pos == "learned":
            pos = self.param("pos_embed", nn.initializers.normal(0.02), (1, s, self.dim))
            x = x + pos.astype(self.dtype)
        elif self.pos not in ("rope", "none"):
            raise ValueError(
                f"unknown pos {self.pos!r}; use 'rope', 'learned' or 'none'"
            )
        rope = self.pos == "rope"  # applied to q/k inside each block
        attn_fn = self.attn_fn
        if attn_fn is None:
            if self.attn == "flash":
                from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import (
                    flash_attention,
                )

                attn_fn = partial(flash_attention, causal=self.causal,
                                  window=self.window)
            else:
                attn_fn = partial(vanilla_attention, causal=self.causal,
                                  window=self.window)
        if self.pp_stages > 0:
            from distributed_tensorflow_ibm_mnist_tpu.models.transformer import (
                StackedBlocks,
            )

            if self.depth % self.pp_stages:
                raise ValueError(
                    f"depth {self.depth} not divisible by pp_stages {self.pp_stages}"
                )
            if self.dropout > 0.0 or self.moe_every > 0:
                raise ValueError(
                    "pipeline stages need identical per-block programs: "
                    "dropout and MoE blocks don't compose with pp_stages"
                )
            x = StackedBlocks(
                dim=self.dim, heads=self.heads, heads_kv=self.heads_kv,
                n_stages=self.pp_stages,
                per_stage=self.depth // self.pp_stages, mlp_ratio=self.mlp_ratio,
                attn_fn=attn_fn, pipeline_fn=self.pipeline_fn,
                block_remat=self.block_remat, rope=rope,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                dtype=self.dtype, name="pipe_blocks",
            )(x, train=train)
            x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                             name="norm_out")(x)
            if self.tie_embeddings:
                x = embed.attend(x)  # logits = x @ embed^T, weights shared
            else:
                x = nn.Dense(self.num_classes, dtype=self.dtype, name="logits")(x)
            return x.astype(jnp.float32)
        block_cls = (
            nn.remat(TransformerBlock, static_argnums=(2,))
            if self.block_remat and not decode  # remat is a backward-pass
            else TransformerBlock               # lever; decode has no bwd
        )
        # decode/max_len ride as kwargs only when decoding so the training
        # trace (incl. the remat-wrapped class, whose static_argnums cover
        # positional train only) is byte-identical to previous rounds
        extra = (
            {"decode": True, "max_len": max_len, "ragged": ragged}
            if decode else {}
        )
        for i in range(self.depth):
            x = block_cls(
                dim=self.dim, heads=self.heads, heads_kv=self.heads_kv,
                mlp_ratio=self.mlp_ratio,
                dropout=self.dropout, attn_fn=attn_fn,
                use_moe=self.moe_every > 0 and (i + 1) % self.moe_every == 0,
                n_experts=self.n_experts, moe_capacity_factor=self.moe_capacity_factor,
                moe_top_k=self.moe_top_k, moe_z_weight=self.moe_z_weight,
                moe_fn=self.moe_fn, rope=rope, rope_theta=self.rope_theta,
                norm_eps=self.norm_eps, sow_kv=self.sow_kv,
                window=self.window, kv_cache_dtype=self.kv_cache_dtype,
                page_size=self.page_size,
                paged_one_device=self.paged_one_device, quant=self.quant,
                dtype=self.dtype, name=f"block_{i}",
            )(x, train, **extra)
        x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                         name="norm_out")(x)
        if self.tie_embeddings:
            # the tied head reads the (full-precision) embedding table —
            # quantizing it would also quantize the token lookup, so a
            # quant model with tied embeddings keeps its head at full
            # precision (documented in docs/PERFORMANCE.md)
            x = embed.attend(x)  # logits = x @ embed^T, weights shared
        elif self.quant == "int8":
            from distributed_tensorflow_ibm_mnist_tpu.models.quant import Int8Dense

            x = Int8Dense(self.num_classes, dtype=self.dtype, name="logits")(x)
        else:
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="logits")(x)
        return x.astype(jnp.float32)
