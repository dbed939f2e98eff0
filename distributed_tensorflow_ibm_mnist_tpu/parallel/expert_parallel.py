"""Expert parallelism: top-k MoE (Switch top-1 / GShard top-k) with
all_to_all dispatch.

The reference had no MoE (SURVEY.md §2.3); this completes the rebuild's
parallelism-strategy inventory.  Design follows the Switch/GShard recipe,
shaped for the MXU: routing produces a STATIC-shaped ``(tokens, experts,
capacity)`` dispatch tensor, so dispatch and combine are two einsums (dense
matmuls, no scatter/gather, no dynamic shapes), and expert FFNs are one
batched matmul over the expert dimension.

Distribution: with ``E`` total experts over an ``A``-way mesh axis, each
shard owns ``E/A`` experts and routes its local tokens to ALL experts; one
:func:`~...collectives.all_to_all` moves each expert's capacity buffers to
the shard that owns it, the expert FFNs run, and the reverse all_to_all
brings results home (SURVEY.md §2.4's transposing collective).  Tokens
beyond an expert's capacity are dropped (standard Switch semantics) — size
capacity with :func:`expert_capacity` to bound drops.

Gradient path: the gate probability multiplies the combined output, so the
router trains through the same loss (plus the standard load-balancing
auxiliary loss, returned separately).

The serving side (:func:`sigmoid_topk_route`, :func:`dropless_held_ffn`) is
the other contract: ONE chip's share of a wide expert-parallel deployment.
The layer is told which experts it HOLDS (``first`` and the banks' leading
axis), routes every token over ALL experts, keeps the (token, choice) pairs
whose expert it holds, groups them by expert and runs one grouped matrix
product per projection over the experts held (a Pallas kernel, JAX's own
``megablox.gmm``: measured against ``jax.lax.ragged_dot`` on a v5e it took
1.20 against 1.74 ms a layer at 96 decoding rows and 3.79 against 4.71 ms
at a 2048-token chunk, PERF.md §6 PR 32, so it is the one kept).  It
returns that PARTIAL sum: what the absent experts would add is another
chip's, and nothing here stands in for those chips or for their exchange.  No capacity, no drop: a
pair is computed whatever the imbalance.  The capacity-dropping
:class:`MoEBlock` above stays the trainer's.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import resolve_interpret
from distributed_tensorflow_ibm_mnist_tpu.parallel import collectives as cl
from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import shard_map_compat

_GMM_TILES = (128, 1024, 1024)  # rows x contraction x columns of one step of
#   the grouped product: the best of (128, 1024, 1024), (256, 2048, 512) and
#   (512, 1024, 1024) at 16 banks of 4096 x 2048 on a v5e


def expert_capacity(n_tokens: int, n_experts: int, factor: float = 1.25) -> int:
    """Per-expert buffer size for ``n_tokens`` routed across ``n_experts``."""
    return max(1, int(n_tokens * factor / n_experts))


def _route(x, w_router, n_experts: int, capacity: int, top_k: int = 1):
    """Top-k routing -> (dispatch (T,E,C), combine (T,E,C), aux_loss
    ingredients, stats).

    ``top_k=1`` is Switch; ``top_k>1`` is the GShard recipe: each token's
    k chosen experts get a buffer slot in CHOICE-PRIORITY order (all first
    choices fill before any second choice — a token's secondary pick is
    the first dropped under pressure), and the combine weights are the
    top-k router probabilities normalized over the k choices (fixed before
    capacity; a capacity-dropped choice simply contributes nothing).
    Everything stays static-shaped: k one-hot rounds unrolled at trace
    time, dispatch/combine remain two dense einsums.

    ``stats`` (VERDICT.md r3 item 5 — capacity overflow was silent):

    * ``dropped`` — fraction of the T*top_k (token, choice) assignments
      that found no buffer slot.  An undersized ``capacity_factor`` now
      shows up as a nonzero ``moe_dropped_frac`` metric instead of just
      training worse.
    * ``z`` — mean squared router logsumexp (the ST-MoE router z-loss
      ingredient; penalizing it keeps router logits small and routing
      stable).  Returned raw; the caller weights it.
    """
    if not 1 <= top_k <= n_experts:
        raise ValueError(
            f"top_k must be in [1, n_experts={n_experts}], got {top_k}"
        )
    logits = x @ w_router  # (T, E)
    logits32 = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits32, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    if top_k == 1:
        gates = topk_probs  # Switch: the RAW router prob (its gradient
        #   path; renormalizing would collapse it to a constant 1)
    else:
        gates = topk_probs / topk_probs.sum(axis=-1, keepdims=True)
    counts = jnp.zeros((n_experts,), jnp.float32)  # filled slots per expert
    dispatch = jnp.zeros((x.shape[0], n_experts, capacity), jnp.float32)
    combine = jnp.zeros_like(dispatch)
    kept = jnp.zeros((), jnp.float32)
    for c in range(top_k):
        onehot = jax.nn.one_hot(topk_idx[:, c], n_experts, dtype=jnp.float32)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + counts[None, :]) * onehot
        keep = (pos < capacity).astype(jnp.float32) * onehot
        slot = keep[..., None] * jax.nn.one_hot(
            pos.astype(jnp.int32), capacity)  # (T, E, C)
        dispatch = dispatch + slot
        combine = combine + slot * gates[:, c, None, None]
        counts = counts + keep.sum(axis=0)
        kept = kept + keep.sum()
    # load-balancing ingredients from the PRIMARY choice (standard):
    # fraction-of-tokens / mean-router-prob per expert (the caller reduces
    # these across shards BEFORE the product, so the distributed aux loss
    # is exactly the global one)
    frac_tokens = jax.nn.one_hot(
        topk_idx[:, 0], n_experts, dtype=jnp.float32).mean(axis=0)
    frac_probs = probs.mean(axis=0)
    stats = {
        "dropped": 1.0 - kept / (x.shape[0] * top_k),
        "z": jnp.mean(jax.nn.logsumexp(logits32, axis=-1) ** 2),
    }
    return dispatch, combine, (frac_tokens, frac_probs), stats


def sigmoid_topk_route(u, w_router, bias, top_k: int):
    """Sigmoid-scored top-k routing with a selection-only correction bias
    (``noaux_tc`` with one group): ``s = sigmoid(u W_r)`` in float32, the
    ``top_k`` experts with the largest ``s + bias``, weights ``s_e`` over
    the sum of the chosen ``s`` — the bias moves the CHOICE and never the
    weights.  ``u`` (T, D), ``w_router`` (D, E), ``bias`` (E,).  Returns
    ``(ids (T, top_k) int32, weights (T, top_k) float32)``."""
    s = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids.astype(jnp.int32), w / w.sum(-1, keepdims=True)


def dropless_held_ffn(u, ids, weights, w_gate, w_up, w_down, first: int,
                      valid=None):
    """The held experts' part of a top-k expert layer, gated (SwiGLU) or,
    with ``w_gate`` None, ungated squared-ReLU.

    ``u`` (T, D) tokens; ``ids`` / ``weights`` (T, k) each token's chosen
    experts (ids over ALL experts) and their weights; ``w_gate`` / ``w_up``
    (H, D, F) and ``w_down`` (H, F, D) the banks of the ``H`` experts held
    here, global ids ``first .. first + H - 1``; ``valid`` (T,) bool marks
    the real tokens (padding and idle rows route nowhere).

    The T * k pairs are sorted so that the held ones come first, grouped by
    expert; the grouped product multiplies each group by its expert's matrix
    and visits no tile of rows past the last group (its grid is as long as
    the groups are), so the work follows the pairs held and not T * k.
    Returns ``(y (T, D), load (H,) int32)``:
    ``y = sum over the held pairs of w * W_down_e(silu(W_gate_e u) * W_up_e
    u)`` (ungated: ``w * W_down_e relu(W_up_e u)^2``) and the pairs each held
    expert computed.  Dropless: no capacity bounds a group."""
    t, k = ids.shape
    n_held = w_up.shape[0]
    local = ids - first
    held = (local >= 0) & (local < n_held)
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, local, n_held).reshape(-1)
    order = jnp.argsort(key, stable=True)  # held pairs first, by expert
    load = jnp.zeros((n_held,), jnp.int32).at[key].add(1, mode="drop")
    rows = u[order // k]
    pad = -rows.shape[0] % _GMM_TILES[0]  # whole tiles of rows
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    interpret = resolve_interpret(None)

    def grouped(x, bank):  # (rows, K) x (H, K, N) by groups of ``load`` rows
        tiles = tuple(map(min, _GMM_TILES, (x.shape[0],) + bank.shape[1:]))
        return _megablox_gmm(x, bank, load, preferred_element_type=u.dtype,
                             tiling=tiles, interpret=interpret)

    if w_gate is None:
        h = jnp.square(jax.nn.relu(grouped(rows, w_up)))
    else:
        h = nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
    y = grouped(h, w_down)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    y = y[back].reshape(t, k, -1).astype(jnp.float32)
    # rows past the last group were never written: select, do not multiply
    y = jnp.where(held[..., None], y * weights[..., None], 0.0).sum(1)
    return y.astype(u.dtype), load


def _expert_ffn(params, x):
    """Batched expert FFN: x (E, C, D) with per-expert stacked params."""
    h = jnp.einsum("ecd,edh->ech", x, params["w1"]) + params["b1"][:, None]
    h = nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, params["w2"]) + params["b2"][:, None]


def _aux_loss(frac_tokens, frac_probs, n_experts: int):
    """Switch load-balancing loss: E x sum(frac_tokens * frac_probs)."""
    return n_experts * jnp.sum(frac_tokens * frac_probs)


def moe_ffn_local(params, x, n_experts: int, capacity: int, top_k: int = 1):
    """Single-shard MoE forward: ``x`` (T, D) -> (out (T, D), aux_loss,
    stats) with ``stats`` = {"dropped": frac, "z": router z ingredient}."""
    dispatch, combine, fracs, stats = _route(x, params["router"], n_experts,
                                             capacity, top_k)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    expert_out = _expert_ffn(params, expert_in)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.astype(x.dtype), _aux_loss(*fracs, n_experts), stats


def make_moe_dispatch(mesh: Mesh, n_experts: int, capacity: int,
                      axis_name: str = "data", top_k: int = 1):
    """Build the expert-parallel MoE forward as a shard_map island.

    ``moe(params, x) -> (out, aux)`` where ``x`` is (T, D) sharded over
    ``axis_name``, ``params['router']`` is replicated, and the expert-stacked
    leaves (``w1/b1/w2/b2``, leading dim ``n_experts``) are sharded over the
    same axis — each shard OWNS ``n_experts / axis_size`` experts.
    ``capacity`` is per (shard, expert) pair.
    """
    a = mesh.shape[axis_name]
    if n_experts % a:
        raise ValueError(f"n_experts={n_experts} not divisible by |{axis_name}|={a}")

    def local(params, x):
        # x: local (T_local, D); expert params: local (E/A, ...) — this
        # shard's experts.  Route locally to ALL E experts, then all_to_all
        # so each shard runs only its own experts on everyone's tokens.
        dispatch, combine, fracs, stats = _route(x, params["router"], n_experts,
                                                 capacity, top_k)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
        # (E, C, D) -> (E/A, A*C, D): block e of shard s lands on shard owning e
        expert_in = cl.all_to_all(expert_in, axis_name, split_axis=0, concat_axis=1)
        expert_out = _expert_ffn(params, expert_in)
        # reverse: (E/A, A*C, D) -> (E, C, D), capacity buffers back home
        expert_out = cl.all_to_all(expert_out, axis_name, split_axis=1, concat_axis=0)
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
        # global fractions first, THEN the product: exact global aux loss;
        # stats are per-token means over equal-size shards, so their
        # cross-shard mean is exactly the global figure too
        fracs = cl.all_reduce_mean(fracs, axis_name)
        stats = cl.all_reduce_mean(stats, axis_name)
        return out.astype(x.dtype), _aux_loss(*fracs, n_experts), stats

    param_specs = {
        "router": P(),
        "w1": P(axis_name), "b1": P(axis_name),
        "w2": P(axis_name), "b2": P(axis_name),
    }
    return shard_map_compat(
        local, mesh,
        in_specs=(param_specs, P(axis_name, None)),
        out_specs=(P(axis_name, None), P(), {"dropped": P(), "z": P()}),
    )


def make_moe_dispatch_auto(
    mesh: Mesh,
    n_experts: int,
    capacity_factor: float = 2.0,
    axis_name: str = "data",
    top_k: int = 1,
):
    """Shape-adaptive wrapper over :func:`make_moe_dispatch` — the trainer's
    config-driven EP hook (VERDICT.md round-1 item 2: ``make_moe_dispatch``
    was an unreachable island).

    Capacity is derived from the incoming token count at trace time, and
    island-incompatible shapes (the batch-1 init sample, eval remainders
    that don't divide the axis) fall back to the single-shard
    :func:`moe_ffn_local` — same routing math, no all_to_all.
    """
    a = mesh.shape[axis_name]

    def moe(params, x):
        # each token claims top_k slots, so the balanced-routing demand is
        # t*top_k/E per expert — scale capacity by top_k (GShard recipe)
        t = x.shape[0]
        if n_experts % a or t % a:
            cap = expert_capacity(t * top_k, n_experts, capacity_factor)
            return moe_ffn_local(params, x, n_experts, cap, top_k)
        cap = expert_capacity((t // a) * top_k, n_experts, capacity_factor)
        return make_moe_dispatch(mesh, n_experts, cap, axis_name, top_k)(params, x)

    return moe


def moe_expert_rule(axis: str = "data", marker: str = "moe"):
    """Spec rule sharding MoE expert-stacked leaves over ``axis``.

    ``w1/b1/w2/b2`` carry a leading expert dim (see :class:`MoEBlock`);
    sharding it over the same axis the dispatch all_to_all uses means each
    shard OWNS its experts' weights — the expert-parallel memory contract.
    The router stays replicated (every shard routes its own tokens).
    """
    targets = {"w1", "b1", "w2", "b2"}

    def rule(path: tuple[str, ...], leaf) -> P:
        if marker in path and path[-1] in targets and getattr(leaf, "ndim", 0) >= 1:
            return P(axis, *([None] * (leaf.ndim - 1)))
        return P()

    return rule


class MoEBlock(nn.Module):
    """Drop-in MoE FFN block on (B, S, D) activations.

    ``ep_fn`` (from :func:`make_moe_dispatch`) runs it expert-parallel;
    ``None`` computes all experts locally.  Returns the block output; the
    load-balancing aux loss is stored in the ``losses`` collection (flax
    ``sow``) for the trainer to add, the capacity-overflow fraction in
    ``moe_stats`` (surfaced as the ``moe_dropped_frac`` step metric —
    VERDICT.md r3 item 5), and, with ``z_weight > 0``, the PRE-WEIGHTED
    router z-loss in ``zlosses`` (added to the training loss at weight
    1.0 — the knob is ``model_kwargs={"moe_z_weight": 1e-3}``).
    """

    dim: int
    n_experts: int = 8
    hidden_mult: int = 4
    capacity_factor: float = 2.0
    top_k: int = 1  # experts per token: 1 = Switch, >1 = GShard top-k
    z_weight: float = 0.0  # ST-MoE router z-loss coefficient (0 = off)
    ep_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, s, d = x.shape
        h = self.hidden_mult * self.dim
        init = nn.initializers.lecun_normal()
        params = {
            "router": self.param("router", init, (d, self.n_experts)),
            "w1": self.param("w1", init, (self.n_experts, d, h)),
            "b1": self.param("b1", nn.initializers.zeros, (self.n_experts, h)),
            "w2": self.param("w2", init, (self.n_experts, h, d)),
            "b2": self.param("b2", nn.initializers.zeros, (self.n_experts, d)),
        }
        tokens = x.reshape(b * s, d)
        if self.ep_fn is not None:
            out, aux, stats = self.ep_fn(params, tokens)
        else:
            cap = expert_capacity(b * s * self.top_k, self.n_experts,
                                  self.capacity_factor)
            out, aux, stats = moe_ffn_local(params, tokens, self.n_experts,
                                            cap, self.top_k)
        self.sow("losses", "moe_aux", aux)
        self.sow("moe_stats", "dropped_frac", stats["dropped"])
        if self.z_weight > 0.0:
            self.sow("zlosses", "moe_z", self.z_weight * stats["z"])
        return out.reshape(b, s, d)
