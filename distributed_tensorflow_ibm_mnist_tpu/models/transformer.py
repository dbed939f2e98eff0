"""Vision Transformer classifier — the sequence-model family of the zoo.

The reference's model layer was a single MNIST CNN (SURVEY.md §1 L3); the
rebuild adds a transformer so the framework's sequence-parallel machinery
(parallel/ring_attention.py) has a first-class consumer.  Architecture is a
small ViT: patchify -> learned positional embedding -> pre-norm blocks
(MHA + MLP) -> mean-pool -> linear head.

Parallelism hooks:

* ``attn_fn`` — drop-in attention callable ``(q, k, v) -> out`` on
  (B, S, H, D).  ``None`` uses in-module vanilla attention; pass the result
  of :func:`~...parallel.ring_attention.make_ring_attention` to shard the
  sequence over the ``seq`` mesh axis (the callable is a shard_map island,
  so this module stays ordinary GSPMD-jitted code).
* MLP sublayers are named ``dense_0``/``dense_1``, so the Megatron
  alternating TP rule (parallel/tensor_parallel.py) shards them over
  ``model`` with one reduction per block.
* The decode-cache leaves this module sows — dense ``k``/``v``
  ``(B, max_len, H_kv, D)`` slabs (+ int8 ``k_scale``/``v_scale``
  ``(B, max_len, H_kv)``) and paged ``pages_k``/``pages_v``
  ``(n_pages, page_size, H_kv, D)`` pools — all carry the KV-HEAD axis at
  a fixed position, which is what the SERVING tensor-parallel path shards
  (``kv_cache_rule`` in parallel/tensor_parallel.py: heads split over the
  ``tp`` mesh axis, block tables/cursors replicated).  Nothing in this
  module is mesh-aware: under ``InferenceEngine(tp=N)`` the same decode
  code runs SPMD with q/kv projections column-sharded, each chip
  attending over its own H/tp heads against its own cache shard, and one
  psum per attention block (the row-sharded out-projection) — so cache
  layout changes here must keep the head axis intact per leaf.

Compute in ``dtype`` (bf16 default, MXU-friendly); params and logits f32.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax.numpy as jnp

from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import vanilla_attention


def apply_rope(x: jnp.ndarray, theta: float = 10000.0, offset=0) -> jnp.ndarray:
    """Rotary position embedding on (B, S, H, D) queries/keys (D even).

    Pairs dimension d with d + D/2 and rotates each pair by pos * theta^(-2d/D),
    making attention scores a function of RELATIVE position — no learned
    (1, S, dim) table baking the trained length into the checkpoint, and
    graceful length extrapolation (VERDICT.md r2 item 5).  Angles are
    computed in f32 from the GLOBAL sequence axis: under sequence
    parallelism this runs in GSPMD-jitted model code BEFORE the sp island,
    so each shard's positions come from its global iota slice and the
    rotation composes with ring/Ulysses unchanged.

    ``offset`` shifts the positions (may be a traced int32 scalar): the
    KV-cache decode path rotates the current chunk at its absolute
    position ``cache_index + arange(s)``.  A (B,)-shaped ``offset`` gives
    each batch row its own absolute position — the ragged-prompt decode
    path, where row b's cursor sits at its own prompt length.
    """
    b, s, h, d = x.shape
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    off = jnp.asarray(offset, jnp.float32)
    if off.ndim == 0:
        pos = off + jnp.arange(s, dtype=jnp.float32)
        ang = pos[:, None] * freqs[None, :]  # (S, half)
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:  # (B,) per-row offsets
        pos = off[:, None] + jnp.arange(s, dtype=jnp.float32)[None, :]
        ang = pos[..., None] * freqs  # (B, S, half)
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def quantize_kv_int8(x):
    """Symmetric per-(token, head) int8 quantization for the decode cache:
    ``scale = max|x| / 127`` over the head_dim axis, so each cached
    position/head pair carries one f32 scale (1/D the cache's own bytes)
    and the (B, max_len, H_kv, D) payload stores int8 — HALF the HBM
    stream of a bf16 cache, the bandwidth-bound decode's next constant
    factor after GQA (round-5 verdict item 10).

    The scale factors NEVER multiply the cache payload on the read side:
    scores dequantize per (q, k) PAIR (``scores *= k_scale``) and the PV
    contraction folds ``v_scale`` into the probabilities — both D-times
    smaller than dequantizing the cache itself, so the int8 stream rides
    into the MXU through a fused convert.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(xf / scale[..., None])
    return q.astype(jnp.int8), scale


def reset_cache_slots(cache, slot_mask):
    """Zero the decode-cache state of selected batch rows: K/V payloads,
    int8 scales, and the (B,) write cursor of every row where ``slot_mask``
    is True, leaving other rows untouched.

    This is the per-slot reset the continuous-batching serving engine
    (serving/engine.py) runs when it retires a request: the freed slot's
    cursor returns to 0 so an idle slot's lockstep decode steps stay inside
    its own (max_len,) row, and the next admitted request starts from a
    clean row.  Every leaf of the cache pytree is (B, ...)-leading
    (``_decode_attention`` keeps the cursor (B,)-shaped in both ragged
    modes), so one broadcasted ``where`` per leaf suffices — cheap enough
    to jit per retire batch.
    """
    import jax

    mask = jnp.asarray(slot_mask, bool)

    def _reset(leaf):
        m = mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    return jax.tree.map(_reset, cache)


def _attend_cached(q, kc, vc, ksc, vsc, mask, dtype):
    """Score queries against a gathered cache span — the shared tail of the
    dense and paged decode-attention paths.

    ``kc``/``vc`` are (B, L, H_kv, D) cache operands in their STORED dtype
    (int8 payloads convert to ``dtype`` inside the contraction, keeping the
    HBM stream int8-sized); ``ksc``/``vsc`` are the per-(position, head)
    int8 scales or None for native caches; ``mask`` is (B|1, S, L).  The
    int8 scales apply at (q, k)-pair granularity: scores pick up k_scale
    per key position and probabilities fold v_scale before the PV
    contraction — both D-times cheaper than dequantizing the cache, and
    the softmax sees exactly the dequantized scores.  GQA queries score a
    grouped einsum against the hkv-sized cache with no materialized repeat.

    When the cache is int8, the scaled probabilities stay f32 INTO the PV
    einsum (ISSUE 12 satellite / ADVICE.md): ``p * v_scale`` spans the
    scale's dynamic range, so rounding it to bf16 BEFORE the contraction
    compounded the int8 error for bf16 models — the einsum accumulates in
    f32 anyway (``preferred_element_type``), and the int8 payload still
    converts in-register (the HBM stream is unchanged), so keeping p at
    f32 costs no cache bandwidth.  Native caches keep the compute-dtype p
    (bit-identical to every previous round).
    """
    import jax

    b, s, h, d = q.shape
    hkv = kc.shape[2]
    quant = ksc is not None
    scale = d ** -0.5
    kc_op = kc.astype(dtype) if quant else kc
    vc_op = vc.astype(dtype) if quant else vc
    p_dtype = jnp.float32 if quant else dtype
    if hkv != h:
        qg = q.reshape(b, s, hkv, h // hkv, d)
        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, kc_op,
            preferred_element_type=jnp.float32) * scale
        if quant:
            scores = scores * ksc.transpose(0, 2, 1)[:, :, None, None, :]
        scores = jnp.where(mask[:, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        if quant:
            p = p * vsc.transpose(0, 2, 1)[:, :, None, None, :]
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(p_dtype), vc_op,
            preferred_element_type=jnp.float32).reshape(b, s, h, d)
    else:
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kc_op,
            preferred_element_type=jnp.float32) * scale
        if quant:
            scores = scores * ksc.transpose(0, 2, 1)[:, :, None, :]
        scores = jnp.where(mask[:, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        if quant:
            p = p * vsc.transpose(0, 2, 1)[:, :, None, :]
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(p_dtype), vc_op,
            preferred_element_type=jnp.float32)
    return out.astype(dtype)


def _resolve_attn(attn_fn: Callable | None, attn: str) -> Callable:
    """attn_fn (explicit callable, e.g. a ring-attention island) wins; else
    pick by name: 'vanilla' (XLA) or 'flash' (the Pallas kernel) — a string
    so RunConfig/CLI can select it (``--set model_kwargs={'attn':'flash'}``)."""
    if attn_fn is not None:
        return attn_fn
    if attn == "flash":
        from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import flash_attention

        return flash_attention
    if attn == "vanilla":
        return vanilla_attention
    raise ValueError(f"unknown attn {attn!r}; use 'vanilla' or 'flash'")


class TransformerBlock(nn.Module):
    dim: int
    heads: int
    heads_kv: int = 0  # 0 = heads (MHA).  Grouped-query attention: K/V
    #   projected to heads_kv < heads head groups — smaller kv params and a
    #   heads_kv-sized decode cache; the flash kernel routes q-heads to
    #   shared K/V blocks via index maps (no repeat copies)
    mlp_ratio: int = 4
    dropout: float = 0.0
    attn_fn: Callable | None = None
    attn: str = "vanilla"
    use_moe: bool = False
    n_experts: int = 8
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1  # experts per token: 1 = Switch, >1 = GShard top-k
    moe_z_weight: float = 0.0  # router z-loss coefficient (ST-MoE; 0 = off)
    moe_fn: Callable | None = None  # expert-parallel dispatch island (make_moe_dispatch)
    rope: bool = False  # rotary position embedding on q/k (apply_rope) —
    #   set by models whose pos="rope"; runs BEFORE attn_fn so sp islands
    #   receive already-rotated shards with global positions
    window: int = 0  # causal sliding-window attention width (0 = full);
    #   enforced by the model-built attn_fn on the training path and by the
    #   decode mask here; requires a causal family
    sow_kv: bool = False  # sow the (post-rope) K/V into "intermediates" on
    #   the NORMAL forward path — core/generate.py's flash prefill runs the
    #   prompt through the ordinary (flash) attention and assembles the
    #   decode cache from these, instead of attending over the max_len
    #   cache (O(S*max_len) scores, OOM for long prompts)
    kv_cache_dtype: str = "native"  # "native" (= dtype) | "int8": quantized
    #   decode cache with per-(position, head) scales — see quantize_kv_int8
    page_size: int = 0  # >0: PAGED decode cache — K/V live in a shared
    #   (n_pages, page_size, H_kv, D) pool indexed through a per-row
    #   (B, max_len/page_size) block table instead of a dense
    #   (B, max_len, ...) slab; see _paged_decode_attention.  The pool is
    #   engine state (serving/kv_pool.py), never initialized here.
    paged_one_device: bool = False  # the program that decodes through the
    #   pool runs on ONE device.  A module cannot see the mesh, and a Mosaic
    #   kernel inside a jit over several devices is refused, so the serving
    #   engine states this one fact when it clones its decode model
    #   (tp == 1 and cp == 1); it is what lets single-token paged decode
    #   take the ops/paged_attention.py kernel where the shapes allow.
    rope_theta: float = 10000.0  # the rotary base (a model field: a
    #   configuration that publishes another passes it; the default is what
    #   every program compiled with before the field existed)
    norm_eps: float = 1e-6  # LayerNorm epsilon (flax's default, likewise)
    quant: str = "none"  # "int8": WEIGHT-only quantization — every dense
    #   projection in the block (qkv/q_proj/kv_proj/proj/dense_0/dense_1)
    #   becomes an Int8Dense (models/quant.py): int8 kernel + per-output-
    #   channel f32 scale, dequant fused into the matmul.  Params must be
    #   transformed with quantize_params_int8 (the serving engine does
    #   this at upload/swap); norms, embeddings, and MoE experts stay full
    #   precision.  Orthogonal to kv_cache_dtype (weights vs cache).
    dtype: jnp.dtype = jnp.bfloat16

    def _dense(self, features: int, name: str):
        """The block's matmul layer: nn.Dense, or its int8-stored sibling
        under the SAME name (so param trees transfer by name and the
        Megatron TP rule's path matches are unchanged)."""
        if self.quant == "int8":
            from distributed_tensorflow_ibm_mnist_tpu.models.quant import Int8Dense

            return Int8Dense(features, dtype=self.dtype, name=name)
        if self.quant != "none":
            raise ValueError(
                f"quant must be 'none' or 'int8', got {self.quant!r}")
        return nn.Dense(features, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 max_len: int = 0, ragged: bool = False):
        b, s, _ = x.shape
        head_dim = self.dim // self.heads

        h = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                         name="norm_attn")(x)
        hkv = self.heads_kv or self.heads
        if hkv == self.heads:
            qkv = self._dense(3 * self.dim, "qkv")(h)
            qkv = qkv.reshape(b, s, 3, self.heads, head_dim)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            if self.heads % hkv:
                raise ValueError(
                    f"heads ({self.heads}) must be a multiple of heads_kv ({hkv})"
                )
            # GQA: separate projections — q at full width, k/v at the
            # grouped width (the param saving IS the feature).  Named
            # q_proj/kv_proj for the Megatron TP rule.
            q = self._dense(self.dim, "q_proj")(h)
            kv = self._dense(2 * hkv * head_dim, "kv_proj")(h)
            q = q.reshape(b, s, self.heads, head_dim)
            kv = kv.reshape(b, s, 2, hkv, head_dim)
            k, v = kv[:, :, 0], kv[:, :, 1]
        if decode:
            o = self._decode_attention(q, k, v, max_len, ragged)
        else:
            if self.rope:
                q = apply_rope(q, self.rope_theta)
                k = apply_rope(k, self.rope_theta)
            if self.sow_kv:
                # absolute-position-rotated K/V, exactly what the decode
                # cache stores — the flash-prefill capture point
                self.sow("intermediates", "kv_cache", (k, v))
            o = _resolve_attn(self.attn_fn, self.attn)(q, k, v)
        o = o.reshape(b, s, self.dim)
        o = self._dense(self.dim, "proj")(o)
        if self.dropout > 0.0:
            o = nn.Dropout(self.dropout, deterministic=not train)(o)
        x = x + o

        h = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                         name="norm_mlp")(x)
        # MoE blocks decode too (round 4): routing is per-call — the decode
        # step routes its B current tokens with capacity sized for B, the
        # standard MoE serving semantics (equal to full-forward logits
        # whenever capacity drops nothing; under pressure the per-step
        # routing drops differently than a full-sequence pass would).
        # Aux-loss/stat sows are no-ops outside mutable collections.
        if self.use_moe:
            from distributed_tensorflow_ibm_mnist_tpu.parallel.expert_parallel import MoEBlock

            h = MoEBlock(
                dim=self.dim, n_experts=self.n_experts, hidden_mult=self.mlp_ratio,
                capacity_factor=self.moe_capacity_factor, top_k=self.moe_top_k,
                z_weight=self.moe_z_weight, ep_fn=self.moe_fn, name="moe",
            )(h, train=train)
        else:
            h = self._dense(self.mlp_ratio * self.dim, "dense_0")(h)
            h = nn.gelu(h)
            h = self._dense(self.dim, "dense_1")(h)
        if self.dropout > 0.0:
            h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return x + h

    def _decode_attention(self, q, k, v, max_len: int, ragged: bool = False):
        """Incremental (KV-cache) attention for autoregressive decoding.

        Appends this call's K/V at the running per-row ``cache_index`` (a
        (B,) int32 cursor in the flax ``cache`` collection, mutated via
        ``mutable=["cache"]``) and attends each query causally over its
        row's filled prefix.  Handles S >= 1, so one call prefills a whole
        prompt and subsequent S=1 calls decode — the core/generate.py
        contract.  The cursor being per-row is what makes RAGGED prompts
        work: after a right-padded prefill each row's cursor starts at its
        own prompt length, new K/V land at per-row positions (vmapped
        ``dynamic_update_slice``), RoPE rotates at per-row absolute
        offsets, and the causal mask ``k_pos <= cursor`` keeps every row
        from seeing the pad garbage beyond its own prefix.

        ``ragged`` is STATIC: the per-row machinery (scatter-shaped cache
        writes, (B, S, half) rotation angles, (B, S, max_len) mask)
        measures ~20% of batched decode throughput at B=8 (r4: 18%
        single-shot, r5: 22% median — docs/PERFORMANCE.md), so the
        uniform case — ``prompt_lens=None``,
        including EOS-stopped batches, whose cursors advance in lockstep
        — keeps the scalar-cursor path (one ``dynamic_update_slice``,
        shared angles, (S, max_len) mask).  The cursor variable stays
        (B,)-shaped in both modes so the cache pytree is
        layout-compatible.

        Dtype policy matches the flash kernel (ops/flash_attention.py):
        native-dtype MXU operands with f32 accumulation
        (``preferred_element_type``) — decode is cache-bandwidth-bound, so
        upcasting the whole (B, max_len, H_kv, D) cache to f32 per step
        (the round-3 form) doubled the bytes read of the dominant stream.
        Softmax stays f32.

        The sp/ring ``attn_fn`` islands and the flash kernel are
        training/prefill machinery; decode is bandwidth-bound
        gather-attend over the cache, which XLA handles directly (no
        custom kernel needed at this scale).  Windowed models gather
        only the live W-span of the cache per step — O(W) instead of
        O(max_len) (the r3 advisor's noted cost) — at a shared start on
        the uniform path and at per-row starts (vmapped slices) on the
        ragged path (round 5); full-attention decodes score the whole
        filled prefix.
        """
        if max_len <= 0:
            raise ValueError("decode=True needs max_len > 0 (the KV-cache size)")
        if self.kv_cache_dtype not in ("native", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'native' or 'int8', got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.page_size > 0:
            return self._paged_decode_attention(q, k, v, max_len)
        b, s, h, d = q.shape
        hkv = k.shape[2]  # GQA: the cache is heads_kv-sized — the memory win
        quant = self.kv_cache_dtype == "int8"
        store = jnp.int8 if quant else self.dtype
        cache_k = self.variable(
            "cache", "k", lambda: jnp.zeros((b, max_len, hkv, d), store))
        cache_v = self.variable(
            "cache", "v", lambda: jnp.zeros((b, max_len, hkv, d), store))
        if quant:
            scale_k = self.variable(
                "cache", "k_scale",
                lambda: jnp.zeros((b, max_len, hkv), jnp.float32))
            scale_v = self.variable(
                "cache", "v_scale",
                lambda: jnp.zeros((b, max_len, hkv), jnp.float32))
        idx_var = self.variable(
            "cache", "index", lambda: jnp.zeros((b,), jnp.int32))
        idx = idx_var.value  # (B,) per-row decode cursor
        import jax

        if ragged:
            if self.rope:
                q = apply_rope(q, self.rope_theta, offset=idx)
                k = apply_rope(k, self.rope_theta, offset=idx)
            if s == 1:
                row_update = jax.vmap(
                    lambda c, u, i: jax.lax.dynamic_update_slice(
                        c, u, (i,) + (0,) * (c.ndim - 1)))
            else:
                # multi-token ragged chunks (speculative verify windows,
                # core/generate.py make_verify_window): per-POSITION
                # clamped scatter, NOT a dynamic_update_slice — DUS clamps
                # the chunk's START, so a row overrunning max_len (a
                # retiring row within k-1 of its budget in a tight cache)
                # would have its whole chunk SHIFTED back over real
                # history.  Clamping each position piles the overflow onto
                # max_len-1 instead, which never holds live data (the
                # admission contract prompt+max_new <= max_len puts the
                # last real position at max_len-2), so within-budget
                # positions stay exact — the same overrun contract the
                # paged write path already has.
                rows_ = jnp.arange(b)[:, None]
                pos_ = jnp.minimum(
                    idx[:, None] + jnp.arange(s), max_len - 1)

                def row_update(c, u, i):
                    del i  # positions are precomputed (and clamped) above
                    return c.at[rows_, pos_].set(u.astype(c.dtype))
            if quant:
                k_st, k_sc = quantize_kv_int8(k)
                v_st, v_sc = quantize_kv_int8(v)
                scale_k.value = row_update(scale_k.value, k_sc, idx)
                scale_v.value = row_update(scale_v.value, v_sc, idx)
            else:
                k_st, v_st = k.astype(store), v.astype(store)
            cache_k.value = row_update(cache_k.value, k_st, idx)
            cache_v.value = row_update(cache_v.value, v_st, idx)
            q_pos = idx[:, None] + jnp.arange(s)  # (B, S) absolute positions
        else:
            idx0 = idx[0]  # uniform rows: ONE cursor, one slice update
            if self.rope:
                q = apply_rope(q, self.rope_theta, offset=idx0)
                k = apply_rope(k, self.rope_theta, offset=idx0)
            if quant:
                k_st, k_sc = quantize_kv_int8(k)
                v_st, v_sc = quantize_kv_int8(v)
                scale_k.value = jax.lax.dynamic_update_slice(
                    scale_k.value, k_sc, (0, idx0, 0))
                scale_v.value = jax.lax.dynamic_update_slice(
                    scale_v.value, v_sc, (0, idx0, 0))
            else:
                k_st, v_st = k.astype(store), v.astype(store)
            cache_k.value = jax.lax.dynamic_update_slice(
                cache_k.value, k_st, (0, idx0, 0, 0))
            cache_v.value = jax.lax.dynamic_update_slice(
                cache_v.value, v_st, (0, idx0, 0, 0))
            q_pos = (idx0 + jnp.arange(s))[None]  # (1, S) broadcasts over B
        # saturate the cursor at max_len: decode-ahead windows (serving
        # engine decode_ahead=k) legitimately run a retiring row up to k-1
        # steps past its budget before the host sees the EOS/budget stop,
        # so a full-budget row (prompt + max_new == max_len) may decode
        # past the cache end.  dynamic_update_slice already clamps the
        # WRITE start; clamping the cursor too keeps RoPE offsets and mask
        # positions bounded for those garbage steps (the row is reset at
        # retirement — wasted FLOPs, never corruption).  A no-op for every
        # well-behaved row: prompt + max_new <= max_len is the admission
        # contract.
        idx_var.value = jnp.minimum(idx + s, max_len)

        kc, vc = cache_k.value, cache_v.value
        ksc = scale_k.value if quant else None
        vsc = scale_v.value if quant else None
        k_pos = jnp.arange(max_len)[None]  # (1, max_len) absolute positions
        if self.window and (self.window + s - 1) < max_len:
            # windowed decode gathers only the live span instead of
            # scoring the whole max_len cache (the O(max_len)-per-step
            # cost noted by the r3 advisor): queries [cursor, cursor+s)
            # attend at most positions (cursor+s-1-W, cursor+s) — a
            # static W+s-1 span starting at max(cursor-W+1, 0).  The
            # span's end never exceeds cursor+s <= max_len (the cache
            # contract), so the dynamic_slice start is exact, and masking
            # the gathered span with its true positions keeps the
            # full-cache softmax's exact support (numerically equivalent;
            # reduction trees over span vs max_len elements round ~1e-7
            # apart, so not bit-identical).  Ragged rows (round 5) gather
            # at PER-ROW starts — a vmapped dynamic_slice at each row's
            # own cursor — so window composes with prompt_lens instead of
            # falling back to the O(max_len) full-cache score.
            span = self.window + s - 1
            if ragged:
                start = jnp.maximum(idx - self.window + 1, 0)  # (B,)
                row_slice = jax.vmap(
                    lambda c, st: jax.lax.dynamic_slice(
                        c, (st,) + (0,) * (c.ndim - 1),
                        (span,) + c.shape[1:]))
                kc = row_slice(kc, start)
                vc = row_slice(vc, start)
                if quant:
                    ksc = row_slice(ksc, start)
                    vsc = row_slice(vsc, start)
                k_pos = start[:, None] + jnp.arange(span)  # (B, span)
            else:
                start = jnp.maximum(idx0 - self.window + 1, 0)
                kc = jax.lax.dynamic_slice(
                    kc, (0, start, 0, 0), (b, span, hkv, d))
                vc = jax.lax.dynamic_slice(
                    vc, (0, start, 0, 0), (b, span, hkv, d))
                if quant:
                    ksc = jax.lax.dynamic_slice(
                        ksc, (0, start, 0), (b, span, hkv))
                    vsc = jax.lax.dynamic_slice(
                        vsc, (0, start, 0), (b, span, hkv))
                k_pos = (start + jnp.arange(span))[None]  # (1, span)
        mask = k_pos[:, None, :] <= q_pos[:, :, None]  # (B|1, S, span|max_len)
        if self.window:
            mask &= k_pos[:, None, :] > q_pos[:, :, None] - self.window
        return _attend_cached(q, kc, vc, ksc, vsc, mask, self.dtype)

    def _paged_decode_attention(self, q, k, v, max_len: int):
        """Paged decode attention: K/V live in a POOLED
        ``(n_pages, page_size, H_kv, D)`` slab per layer, and each batch row
        owns a ``(max_len / page_size,)`` row of the ``block_table`` mapping
        its virtual positions to pool pages.  Memory then scales with LIVE
        tokens (pages allocated on admission, freed on retirement) instead
        of ``slots * max_len``, and read-only pages can be SHARED between
        rows (the radix prefix cache, serving/radix_cache.py) because this
        path writes only the current chunk's positions — never a whole row.

        Writes scatter each new K/V position to ``(block_table[pos // ps],
        pos % ps)``.  Reads take one of two forms, chosen from what this
        call can observe (never a knob):

        * a single-token step (``s == 1``) of a one-device program
          (``paged_one_device``) over a pool that stores the compute dtype
          in a shape the kernel reads (``ops.paged_attention.
          paged_kernel_eligible``: head dim 128, whole sublane tiles a
          page) runs the Pallas kernel: each row's block table is walked
          only as far as ``min(cursor + 1, max_len)``, so a step reads the
          rows' LIVE pages and nothing else;
        * everything else — multi-token chunks (suffix extend, chunked
          prefill, speculative verify), int8 pools with their scales,
          tp/cp-sharded pools, small head dims — gathers the row's full
          virtual span ``pool[block_table]`` back to (B, max_len, H_kv, D)
          and reuses the dense tail (same mask, same reduction shapes),
          which is what makes paged greedy decoding token-identical to the
          dense layout there.

        Both score the same support with the same arithmetic (compute-dtype
        operands, f32 scores and accumulation); the kernel's online softmax
        rounds in a different order, so the two agree to rounding, not bit
        for bit.  ``max_len`` must be a page multiple so the virtual span
        is exactly max_len.  Write positions clamp at max_len - 1 exactly
        like the dense path's ``dynamic_update_slice`` clamp (decode-ahead
        overrun rows); unallocated block-table entries point at the
        reserved trash page 0, whose garbage is never exposed: a row's mask
        only admits positions below its cursor, all of which lie in
        allocated pages (the kernel never even fetches past them).

        The pool, block table, and cursor are ENGINE state: the init fns
        raise, because pool size is serving configuration
        (serving/kv_pool.py builds it), not a model attribute.  Sliding
        windows are rejected — the windowed span slice assumes dense
        contiguity.
        """
        import jax

        ps = self.page_size
        if max_len % ps:
            raise ValueError(
                f"paged decode needs max_len ({max_len}) to be a multiple "
                f"of page_size ({ps})")
        if self.window:
            raise ValueError(
                "paged decode does not compose with sliding-window "
                "attention (window > 0) — the windowed span gather assumes "
                "a dense contiguous cache row")
        b, s, h, d = q.shape
        hkv = k.shape[2]
        quant = self.kv_cache_dtype == "int8"
        store = jnp.int8 if quant else self.dtype

        def _external(name):
            def init():
                raise ValueError(
                    f"paged decode cache variable {name!r} must be supplied "
                    "by the caller — the page pool is engine state; build "
                    "it with serving.kv_pool.init_paged_cache")
            return init

        pages_k = self.variable("cache", "pages_k", _external("pages_k"))
        pages_v = self.variable("cache", "pages_v", _external("pages_v"))
        if quant:
            scale_k = self.variable(
                "cache", "pages_k_scale", _external("pages_k_scale"))
            scale_v = self.variable(
                "cache", "pages_v_scale", _external("pages_v_scale"))
        bt_var = self.variable("cache", "block_table", _external("block_table"))
        idx_var = self.variable("cache", "index", _external("index"))
        idx = idx_var.value  # (B,) per-row decode cursor
        bt = bt_var.value  # (B, max_len // ps) page ids into the pool

        if self.rope:
            q = apply_rope(q, self.rope_theta, offset=idx)
            k = apply_rope(k, self.rope_theta, offset=idx)
        # write positions, clamped like the dense path's update-slice clamp
        pos = jnp.minimum(idx[:, None] + jnp.arange(s), max_len - 1)  # (B, S)
        page = jnp.take_along_axis(bt, pos // ps, axis=1)  # (B, S)
        off = pos % ps
        if quant:
            k_st, k_sc = quantize_kv_int8(k)
            v_st, v_sc = quantize_kv_int8(v)
            scale_k.value = scale_k.value.at[page, off].set(k_sc)
            scale_v.value = scale_v.value.at[page, off].set(v_sc)
        else:
            k_st, v_st = k.astype(store), v.astype(store)
        pages_k.value = pages_k.value.at[page, off].set(k_st)
        pages_v.value = pages_v.value.at[page, off].set(v_st)
        q_pos = idx[:, None] + jnp.arange(s)  # (B, S), unclamped (dense parity)
        idx_var.value = jnp.minimum(idx + s, max_len)

        if s == 1 and self.paged_one_device:
            from distributed_tensorflow_ibm_mnist_tpu.ops.paged_attention import (
                paged_decode_attention, paged_kernel_eligible)

            if paged_kernel_eligible(q.dtype, pages_k.value.dtype, ps, hkv, d):
                # the current token is already in its page: the row attends
                # cursor + 1 positions (max_len for an overrun row, whose
                # write was clamped onto max_len - 1)
                o = paged_decode_attention(
                    q[:, 0], pages_k.value, pages_v.value, bt,
                    jnp.minimum(idx + 1, max_len))
                return o[:, None]

        # gather the virtual row: (n_pages, ps, ...)[bt] -> (B, n_row, ps, ...)
        kc = pages_k.value[bt].reshape(b, max_len, hkv, d)
        vc = pages_v.value[bt].reshape(b, max_len, hkv, d)
        ksc = scale_k.value[bt].reshape(b, max_len, hkv) if quant else None
        vsc = scale_v.value[bt].reshape(b, max_len, hkv) if quant else None
        k_pos = jnp.arange(max_len)[None]
        mask = k_pos[:, None, :] <= q_pos[:, :, None]  # (B, S, max_len)
        return _attend_cached(q, kc, vc, ksc, vsc, mask, self.dtype)


class StackedBlocks(nn.Module):
    """The ViT block stack with params stacked ``(n_stages, per_stage, ...)``.

    The pipeline-parallel form of the block stack (VERDICT.md round-1 item
    2): one pytree param ``stacked`` holds every block's weights with a
    leading stage axis, so the GPipe island (parallel/pipeline.py) can shard
    stages over the ``pipe`` mesh axis and each device materializes only its
    own stage.  ``pipeline_fn(stage_fn, stacked, x)`` is the trainer-supplied
    hook that wraps ``stage_fn`` (scan this stage's blocks) in the shard_map
    pipeline — or falls back to a local scan for island-incompatible shapes
    (init samples, eval remainders).  With no hook, the stack is a plain
    ``lax.scan`` over all stages: numerically the unstacked ViT with
    identically-distributed (but differently-keyed) initialization.

    Restrictions inherited from the equal-shape pipeline contract: no
    dropout, no MoE blocks in the stack (both vary per-block state).
    """

    dim: int
    heads: int
    n_stages: int
    per_stage: int
    heads_kv: int = 0
    mlp_ratio: int = 4
    attn_fn: Callable | None = None
    attn: str = "vanilla"
    pipeline_fn: Callable | None = None
    block_remat: bool = False  # jax.checkpoint each block inside the stage
    #   scan: the pipeline's backward keeps only block-boundary residuals
    rope: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        import jax
        from jax import lax

        block = TransformerBlock(
            dim=self.dim, heads=self.heads, heads_kv=self.heads_kv,
            mlp_ratio=self.mlp_ratio,
            dropout=0.0, attn_fn=self.attn_fn, attn=self.attn, rope=self.rope,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            dtype=self.dtype,
        )
        sample = jnp.zeros((1, x.shape[1], self.dim), x.dtype)

        def init_fn(rng):
            keys = jax.random.split(rng, self.n_stages * self.per_stage)
            per = [block.init({"params": k}, sample, train=False)["params"] for k in keys]
            stages = [
                jax.tree.map(
                    lambda *a: jnp.stack(a),
                    *per[s * self.per_stage:(s + 1) * self.per_stage],
                )
                for s in range(self.n_stages)
            ]
            return jax.tree.map(lambda *a: jnp.stack(a), *stages)

        stacked = self.param("stacked", init_fn)
        block_apply = lambda p, c: block.apply({"params": p}, c, train=False)
        if self.block_remat:
            block_apply = jax.checkpoint(block_apply)

        def stage_fn(stage_params, h):
            def body(c, p):
                return block_apply(p, c), None

            out, _ = lax.scan(body, h, stage_params)
            return out

        if self.pipeline_fn is not None:
            return self.pipeline_fn(stage_fn, stacked, x)

        def body(c, ps):
            return stage_fn(ps, c), None

        out, _ = lax.scan(body, x, stacked)
        return out


class VisionTransformer(nn.Module):
    """Patch ViT over (B, H, W, C) images in [0, 1]."""

    patch_size: int = 4
    dim: int = 128
    depth: int = 4
    heads: int = 4
    heads_kv: int = 0  # 0 = heads; <heads = grouped-query attention
    mlp_ratio: int = 4
    num_classes: int = 10
    dropout: float = 0.0
    attn_fn: Callable | None = None
    attn: str = "vanilla"
    moe_every: int = 0  # 0 = dense; k = every k-th block uses a MoE FFN
    n_experts: int = 8
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    moe_z_weight: float = 0.0  # router z-loss coefficient (0 = off)
    moe_fn: Callable | None = None
    pp_stages: int = 0  # >0: stack blocks (n_stages, per_stage, ...) for the
    #                     GPipe island — params shardable over 'pipe'
    pipeline_fn: Callable | None = None  # (stage_fn, stacked_params, x) -> y
    block_remat: bool = False  # jax.checkpoint each block (backward
    #                            recomputes within-block activations; the
    #                            O(depth) memory lever for deep/long-seq runs)
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        p = self.patch_size
        b, h, w, c = x.shape
        if h % p or w % p:
            raise ValueError(f"image {h}x{w} not divisible by patch size {p}")
        x = x.astype(self.dtype)
        # patchify as a stride-p conv: one MXU-friendly matmul over pixels
        x = nn.Conv(
            self.dim, kernel_size=(p, p), strides=(p, p), padding="VALID",
            dtype=self.dtype, name="patch_embed",
        )(x)
        s = (h // p) * (w // p)
        x = x.reshape(b, s, self.dim)
        pos = self.param("pos_embed", nn.initializers.normal(0.02), (1, s, self.dim))
        x = x + pos.astype(self.dtype)
        if self.pp_stages > 0:
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
                attn_fn=self.attn_fn, attn=self.attn, pipeline_fn=self.pipeline_fn,
                block_remat=self.block_remat, dtype=self.dtype, name="pipe_blocks",
            )(x, train=train)
            x = nn.LayerNorm(dtype=self.dtype, name="norm_out")(x)
            x = x.mean(axis=1)
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="logits")(x)
            return x.astype(jnp.float32)
        # static_argnums: (self, x, train) -> train must stay a Python bool
        # through the checkpoint (it selects dropout determinism)
        block_cls = (
            nn.remat(TransformerBlock, static_argnums=(2,))
            if self.block_remat
            else TransformerBlock
        )
        for i in range(self.depth):
            x = block_cls(
                dim=self.dim, heads=self.heads, heads_kv=self.heads_kv,
                mlp_ratio=self.mlp_ratio,
                dropout=self.dropout, attn_fn=self.attn_fn, attn=self.attn,
                use_moe=self.moe_every > 0 and (i + 1) % self.moe_every == 0,
                n_experts=self.n_experts, moe_capacity_factor=self.moe_capacity_factor,
                moe_top_k=self.moe_top_k, moe_z_weight=self.moe_z_weight,
                moe_fn=self.moe_fn, dtype=self.dtype, name=f"block_{i}",
            )(x, train)
        x = nn.LayerNorm(dtype=self.dtype, name="norm_out")(x)
        x = x.mean(axis=1)
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="logits")(x)
        return x.astype(jnp.float32)
