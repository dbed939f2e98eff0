"""Continuous-batching inference engine over the compiled decode path.

``make_generator`` (core/generate.py) compiles an entire prefill+decode
episode into ONE program per (B, P) shape: ideal for offline batches,
wrong for a request STREAM — every row waits for the slowest row's
``max_new`` (head-of-line blocking) and each new shape recompiles.  This
engine is the TF-Replicator / Mesh-TensorFlow answer (PAPERS.md): keep the
DEVICE side a small set of fixed-shape compiled programs and move all the
variable-length multiplexing into a host-side driver loop.

Device side (compiled once each, resident for the engine's lifetime):

* ``len(buckets)`` prefill programs (core/generate.py ``make_prefill`` at
  B=1 per padded bucket length) — the bucket set comes from the scheduler
  (one source of truth; an engine-level ``buckets=`` that disagrees with a
  caller-supplied scheduler is rejected at construction),
* ONE batched decode-ahead WINDOW across all ``slots`` rows
  (``_sample_window_core``: a ``lax.scan`` of ``decode_ahead`` fused
  decode+pick steps, ragged — every slot owns an independent cursor),
* a slot insert (``dynamic_update_slice`` of a prefilled row into the
  (slots, max_len) cache — the slot index is traced, so one compile) and a
  per-slot reset (models/transformer.py ``reset_cache_slots``).

Host loop (:meth:`InferenceEngine.step`): cancel overdue rows → admit
queued requests into free slots (prefill at the request's bucket, pick its
first token) → ONE windowed decode dispatch across ALL slots → retire rows
on EOS / budget, zeroing their cache rows — freed slots refill on the very
next iteration, so no request ever waits on another request's completion.
Idle slots decode garbage into their own rows in lockstep (cache writes
are per-row; the batch shape is fixed) — wasted FLOPs on an un-full
engine, never corruption.

Decode-ahead (ISSUE 5, ``decode_ahead=k``): each dispatch runs k fused
decode+pick steps in-graph against a per-slot active mask FROZEN for the
window, emitting a (slots, k) token block the host reads back ONCE — the
per-token host sync and dispatch tax docs/PERFORMANCE.md §Serving measured
drop ~k×.  Retirement conditions (EOS, budget) are still judged on the
host, so a row that stops mid-window decodes up to k−1 garbage steps past
its stop before the host sees it; those tokens are masked off the output
(never appended, never delivered) and the row's ≤k−1 overrun writes land
only in its own row (models/transformer.py clamps the cursor at max_len) —
the same wasted-FLOPs-never-corruption contract idle slots already have.
Windows are token-identical for every k — greedy because a slot's tokens
depend only on its own cache row and previous token, sampled because the
PRNG key for the token at generated index n is ``fold_in(base_key, n)``
(serving/sampling.py): the index, not the window phase, owns the key, so
decode-ahead width never changes a request's stream.

Per-request sampling (ISSUE 13, top-k ISSUE 14): a request may carry
``SamplingParams(temperature, top_p, top_k, seed)`` (serving/sampling.py);
the engine keeps per-slot (slots,) temperature/top-p/top-k planes and a
(slots, 2) base-key plane as runtime DATA into ONE compiled window program
(core/generate.py ``_sample_window_core``) — greedy and sampled rows ride
the same program, so the compile census is invariant across sampling
mixes; inside it the pick branches on the planes (ISSUE 29), so an
all-greedy window pays for argmax alone and only a window with a top-k or
top-p row sorts the vocabulary.  Each generated token's raw-logits
logprob comes back with the token block (``Request.logprobs``), and a
request's stream is a pure function of its seed — restarts and failover
replays are token-identical.

Two more host-loop latencies hide behind the window (ISSUE 5):

* **Prefix cache** (``prefix_cache_bytes=``, serving/prefix_cache.py) — a
  byte-bounded LRU keyed by blake2b over the (bucket, prompt) pair; a hit
  reuses the stored prefill row + last-position logits and skips the
  prefill dispatch entirely.  Sampling-safe: the cache stores only the
  DETERMINISTIC prefill products, and every admission (hit or miss) picks
  its own first token from the logits with its own request's params
  through the shared ``first_pick`` program (serving/sampling.py).
* **Prefill overlap** — after dispatching a window and BEFORE blocking on
  its readback, the engine pops the next queued request and dispatches its
  bucketed B=1 prefill, so prefill compute overlaps the in-flight window
  instead of stalling every slot.  The prefilled request parks in a
  pending queue (bounded by ``slots``) and lands in the next free slot;
  a pending request whose deadline lapses before landing is cancelled at
  landing time (the prefill was the overlap gamble's stake).

Speculative decoding (ISSUE 9, ``speculative="ngram"``): the decode-ahead
window still emits ONE token per model step — k tokens cost k sequential
forwards.  Speculative mode replaces the window with its verify sibling
(core/generate.py ``make_verify_window``): between dispatches the host
drafts up to ``draft_len`` continuation tokens per slot with a model-free
prompt-lookup drafter (serving/drafter.py — suffix n-gram match over the
request's own prompt + generated stream), and ONE (slots, draft_len+1)-
position target forward verifies the whole chunk.  Greedy rows accept
the longest drafted prefix the model's own argmax reproduces plus one
free correction token — output is token-identical to plain greedy decode
by construction (the emitted tokens ARE the argmax chain), pinned across
dense/paged/int8 layouts in tests/test_speculative.py.  Sampled rows use
speculative REJECTION sampling (core/generate.py ``_verify_sample_core``,
ISSUE 13): draft token i is accepted with probability
min(1, p_target(i)/q_draft(i)) and the first rejection resamples from
the residual distribution, so the emitted marginal equals sampling the
target directly (chi-squared gated in tests/test_sampling.py) and the
stream stays a pure function of the request's seed at fixed engine
config (replays are token-identical; the spec and plain sample PATHS
differ — only their distributions and the greedy limit coincide).
Every accepted lane is a sequential forward the
engine didn't run; a rejected lane costs a wasted verify position, never
a wrong token.  The KV cursor is rewound in-graph to the acceptance
point, so rejected positions are garbage the next window overwrites —
the same wasted-FLOPs-never-corruption contract as decode-ahead overrun,
on both layouts (paged allocation already budgets len+max_new; ISSUE 7).
Incompatible with sliding-window attention (rejected at construction).
The chaos contract is unchanged: one
``serving-step`` event per window dispatch, whether that window decodes
or verifies.  ``ServingStats`` gains drafted/accepted/corrected counters,
``accept_rate``, and ``useful_tokens_per_window``; each request's trace
track gains per-window draft/verify/accept spans.

Chunked prefill (ISSUE 14, ``prefill_chunk=C``): whole-prompt prefill —
bucketed OR radix-suffix — freezes every co-resident request's decode for
the full prompt duration, and long prompts need a matching bucket.  With
``prefill_chunk=C`` (paged KV required) admission allocates the request's
pages up front but dispatches NO prefill; the prompt then advances in
fixed (1, C)-token chunks through the paged suffix-extend program — ONE
``extend[b{C}]`` program for every chunk of every prompt, so the census
stays pinned and prompts up to ``max_len - max_new`` need no bucket.  One
chunk dispatches per engine iteration at the prefill-overlap seam
(between the window dispatch and its blocking readback), so the decode
latency any admission adds is bounded by one chunk, not one prompt.  The
partially-prefilled slot holds a transient PREFILLING state: occupied
(its pages are real) but inactive in every window — its decode writes
are garbage the chunk cursor overwrites — and invisible to drafting and
the token loop.  A radix partial hit lands chunking AT the divergence
page (``done`` starts at the matched-page boundary); the finished prompt
donates its pages back to the trie exactly like whole-prompt admission.
Chaos contract unchanged: one ``serving-admit`` event per admission
attempt (a pool-stall retry does not re-fire), chunk dispatches ride the
window's ``serving-step`` with NO events of their own.  The prefix cache
(whole-row store) is refused under chunking — the radix trie is the
prefix-sharing mechanism.

Launch-path prewarm (ROADMAP item 5a, :meth:`InferenceEngine.prewarm`):
every program above compiles lazily at first use, so the first requests
eat the whole compile bill as TTFT.  ``prewarm()`` runs the engine's full
program family once with dummy inputs before traffic — the persistent
compile cache (utils/compile_cache.py) keeps those compiles across
processes, and ``Router.prewarm()`` fans the warmup across replicas.

Greedy decode through this loop is token-for-token identical to
``make_generator`` for every ``decode_ahead`` (both run the same
``_prefill_core``/``_decode_step_core`` math; pinned in
tests/test_serving.py and tests/test_decode_ahead.py).

Failure hardening (ISSUE 3): failures are isolated at the blast radius
they actually have.  A fault belonging to ONE request — its prefill
raising (poisoned prompt, injected ``serving-admit`` chaos) or its user
``callback`` raising — moves that request to the terminal ``FAILED``
state (``Request.error`` records why), resets its cache row, and the loop
keeps serving every other slot.  A fault in the BATCHED decode dispatch
belongs to all slots: with ``stall_timeout_s`` set, decode exceptions are
absorbed as no-progress iterations until the watchdog deadline, then the
engine fails the in-flight requests and raises :class:`EngineStalled`
cleanly (slots cleared, engine reusable); without a watchdog, the first
decode fault fails in-flight requests and re-raises immediately.
``drain()`` (serve everything already accepted, admit nothing new) and
``close()`` (cancel queued + in-flight, emit stats, refuse further use)
give supervisors graceful-shutdown semantics.  Chaos sites
``serving-admit`` / ``serving-step`` / ``serving-callback``
(utils/chaos.py) inject all three failure shapes on a seeded schedule;
per-site event indices are unchanged by decode-ahead and overlap (one
``serving-admit`` event per admission attempt in FIFO order, one
``serving-step`` event per window dispatch).

Thread model: the engine itself is single-threaded — ONE thread (the
caller's loop, or one daemon pump thread per replica in
serving/daemon.py) drives ``step()``/``step_chunk()`` and owns every
slot/cache mutation.  Cross-thread ``submit()`` is the daemon's job: it
serializes admissions under its tier lock and the scheduler's deque
append/popleft are atomic under CPython, so the pump can pop while a
producer appends.  The only engine state other threads read directly is
:attr:`heartbeat_t` (a single float write, torn-read-free) — the
external liveness probe for a wedged pump.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.core.generate import (
    _sample_window_core,
    _verify_sample_core,
    _zeros_like_shapes,
    cache_shapes,
    make_prefill,
    pick_work,
)
from distributed_tensorflow_ibm_mnist_tpu.models.quant import quantize_params_int8
from distributed_tensorflow_ibm_mnist_tpu.models.transformer import reset_cache_slots
from distributed_tensorflow_ibm_mnist_tpu.ops.flash_attention import flash_attention
from distributed_tensorflow_ibm_mnist_tpu.ops.paged_attention import paged_kernel_eligible
from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import shard_map_compat
from distributed_tensorflow_ibm_mnist_tpu.parallel.ring_attention import (
    make_ring_attention,
)
from distributed_tensorflow_ibm_mnist_tpu.parallel.tensor_parallel import (
    kv_cache_rule,
    make_param_specs,
    megatron_rule,
    mesh_shardings,
    per_chip_bytes,
    serving_mesh,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.drafter import NgramDrafter
from distributed_tensorflow_ibm_mnist_tpu.serving.kv_pool import (
    KVPagePool,
    bt_install,
    gather_page,
    make_paged_extend,
    make_paged_insert,
    page_write,
    paged_cache_shapes,
    paged_reset,
    pages_needed,
    pool_page_bytes,
    pool_page_leaves,
)
from distributed_tensorflow_ibm_mnist_tpu.serving import kv_handoff
from distributed_tensorflow_ibm_mnist_tpu.serving.prefix_cache import PrefixCache
from distributed_tensorflow_ibm_mnist_tpu.serving.radix_cache import RadixCache
from distributed_tensorflow_ibm_mnist_tpu.serving.sampling import (
    SamplingParams,
    base_key,
    first_pick,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.scheduler import FIFOScheduler, Request
from distributed_tensorflow_ibm_mnist_tpu.serving.stats import ServingStats
from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import enable_compile_cache
from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker, host_span

# sentinel "row cache" _prefill_request returns for a radix partial-prefix
# hit: nothing was dispatched — the real work (the suffix-extend program)
# runs at LANDING, against the live trie/pool state at that moment
_RADIX_PREFILL = object()

# sentinel "prefilled" payload a chunked admission parks with when the
# page pool is momentarily dry (ISSUE 14): nothing was prefilled — the
# retry re-runs _chunk_admit from the allocation, skipping the already-
# fired serving-admit chaos event (one event per admission attempt)
_CHUNK_STALL = object()

# sentinel "first token" _paged_land returns on a prefill-role engine
# (ISSUE 16): no token was picked — the landing was packaged into the
# handoff outbox and the slot is already free (its pages moved to the
# packet's hold; the block table gets the caller's reset)
_HANDOFF = object()


class EngineStalled(RuntimeError):
    """The watchdog verdict: no token progress across ALL slots within
    ``stall_timeout_s``.  In-flight requests were already moved to FAILED
    and their slots reset before this raised — the engine object remains
    usable (or closeable) by the caller that catches it."""


def _home_device(params):
    """The chip a single-chip engine lives on: where its params already
    are, else jax's current default device."""
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array) and len(leaf.devices()) == 1:
            return next(iter(leaf.devices()))
    return next(iter(jnp.zeros(()).devices()))


class InferenceEngine:
    """Slot-multiplexed continuous-batching decoder for a causal LM.

    ``slots`` is the resident decode batch (B); ``max_len`` the per-slot
    KV-cache length.  ``scheduler`` defaults to a :class:`FIFOScheduler`
    built from ``buckets=`` (or the stock bucket ladder); pass both a
    scheduler AND ``buckets=`` and they must agree — the scheduler's
    buckets are the compiled prefill shapes.  ``decode_ahead=k`` runs k
    fused decode steps per dispatch/readback (greedy output is
    k-invariant; see the module docs for the waste trade).
    ``speculative="ngram"`` swaps the decode window for the speculative
    verify window: a host-side prompt-lookup drafter proposes up to
    ``draft_len`` tokens per slot per window and one target forward
    accepts greedy rows by argmax match and sampled rows by rejection
    sampling — greedy output stays token-identical to plain decode,
    sampled output stays seed-deterministic and unbiased; exclusive with
    sliding-window attention (see module docs).  ``prefix_cache_bytes``
    arms the prompt prefix cache (sampling-safe — it stores prefill
    logits, never a picked token).

    ``kv_page_size=ps`` switches the decode cache to the PAGED layout
    (serving/kv_pool.py): a fixed pool of ``kv_pages`` pages per layer plus
    per-slot block tables, so HBM scales with LIVE tokens instead of
    ``slots * max_len``.  ``kv_pages`` defaults to dense-equivalent
    capacity; set it LOWER to overcommit (more slots than worst-case
    memory) — a request the pool momentarily cannot hold parks and retries
    (admission stall, never corruption or failure).  ``radix_cache``
    (default on when paged) shares whole prompt-prefix pages between
    requests through a radix trie (serving/radix_cache.py): a matched
    prefix skips its prefill compute (only the suffix runs, via the extend
    program) and occupies ZERO extra pages.  Greedy paged output is
    token-identical to the dense engine for every ``decode_ahead``.
    ``prefill_chunk=C`` (paged only) replaces whole-prompt prefill with
    interleaved C-token chunks through the one ``extend[b{C}]`` program —
    bounded decode stalls, prompts up to ``max_len - max_new`` with no
    matching bucket, a transient PREFILLING slot state (see module docs);
    exclusive with ``prefix_cache_bytes`` (the radix trie is the sharing
    mechanism under chunking).

    ``tp=N`` shards the WHOLE program family over an N-chip ``("tp",)``
    mesh (parallel/tensor_parallel.py ``serving_mesh``): weights
    column/row-split by the same Megatron rule the training mesh uses
    (q/kv/up column, proj/down row — one psum per attention block and one
    per MLP per layer), the KV cache split over the HEAD axis in both
    layouts, per-chip weight and KV bytes 1/tp — a model whose bf16
    weights + pool exceed one chip serves anyway.  ``tp_devices=`` picks
    the chips (default: the first N visible; a router passes each replica
    its own disjoint group).  ``tp`` must divide ``heads`` AND
    ``heads_kv``.  Everything host-side — scheduler, page pool, radix
    trie, prefix keys, the n-gram drafter — never sees the mesh, so
    allocation/admission decisions and greedy output are tp-invariant
    (pinned in tests/test_tp_serving.py), and ``swap_params`` re-shards a
    full host tree onto the engine's own mesh.

    Engine-level sampling knobs (``temperature``/``top_k``/``top_p``/
    ``rng``) set the DEFAULT for requests that carry no
    ``SamplingParams`` (greedy at ``temperature=0``; ``rng`` required
    otherwise — its key data seeds the default base key).  A request's
    own ``submit(..., sampling=SamplingParams(...))`` overrides the
    default per slot — temperature/top_p/top_k/seed are all per-slot
    runtime data planes into the one compiled window (ISSUE 14 made
    top-k a data plane like the rest).
    ``tracer=`` (utils/tracing.Tracer) records a span tree per request and
    per decode window (nil-guarded — zero tracing instructions when None);
    construct it with the same ``clock`` as the engine so span durations
    agree with reported latencies.  Compile accounting is always on:
    ``stats`` reports this engine's ``n_compiled_programs`` /
    ``compile_time_s`` by site (docs/OBSERVABILITY.md).

    Usage::

        eng = InferenceEngine(model, params, slots=4, max_len=128)
        eng.submit([1, 2, 3], max_new=16)
        eng.submit([4, 5], max_new=64, deadline_s=2.0)
        done = eng.run()          # drive until every request retired
        done[0].generated         # real tokens (EOS kept), no pad fill

    The engine is NOT thread-safe: submit and run from one thread (the
    host loop is the single writer of all device state).
    """

    def __init__(self, model, params, *, slots: int, max_len: int,
                 scheduler: FIFOScheduler | None = None,
                 buckets: tuple[int, ...] | None = None,
                 decode_ahead: int = 1,
                 speculative: str | None = None, draft_len: int = 3,
                 prefix_cache_bytes: int = 0,
                 kv_page_size: int = 0, kv_pages: int = 0,
                 radix_cache: bool | None = None,
                 prefill_chunk: int = 0,
                 tp: int = 1, tp_devices=None,
                 cp: int = 1, cp_devices=None,
                 quant: str | None = None,
                 eos_id: int | None = None, pad_id: int = 0,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 min_p: float = 0.0, role: str = "both",
                 rng=None, writer: MetricWriter | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 stall_timeout_s: float | None = None,
                 chaos=None, tracer=None, trace_tid: int = 0,
                 telemetry=None):
        if stall_timeout_s is not None and stall_timeout_s <= 0:
            raise ValueError(
                f"stall_timeout_s must be > 0 (None disables the watchdog), "
                f"got {stall_timeout_s}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(
                f"max_len must be >= 2 (one prompt token + one generated), "
                f"got {max_len}")
        if decode_ahead < 1:
            raise ValueError(
                f"decode_ahead must be >= 1 (1 = one decode step per host "
                f"sync, the classic loop), got {decode_ahead}")
        if speculative not in (None, "ngram"):
            raise ValueError(
                f"speculative must be None or 'ngram' (model-free prompt-"
                f"lookup drafting), got {speculative!r}")
        if speculative is not None:
            if draft_len < 1:
                raise ValueError(
                    f"draft_len must be >= 1 (tokens drafted per verify "
                    f"window), got {draft_len}")
            if getattr(model, "window", 0):
                raise ValueError(
                    "speculative decoding does not compose with sliding-"
                    "window attention (model.window > 0): an overrunning "
                    "verify chunk would mislabel the windowed span gather")
        if eos_id is not None and eos_id == pad_id:
            raise ValueError(
                f"eos_id and pad_id must differ (both {eos_id}): idle slots "
                "are fed pad_id, which must never read as a stop")
        if temperature == 0.0 and (top_k or top_p or min_p):
            raise ValueError(
                "top_k/top_p/min_p filter a SAMPLING distribution; set "
                "temperature > 0")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill', or 'decode', got {role!r}")
        if role != "both" and not kv_page_size:
            raise ValueError(
                "disaggregated roles hand KV off as PAGES — role="
                f"{role!r} needs the paged cache (kv_page_size > 0)")
        if role != "both" and speculative is not None:
            raise ValueError(
                "speculative decoding does not compose with disaggregated "
                "roles yet — the verify family would have to compile on "
                "both sides, voiding the per-role census")
        if temperature != 0.0 and rng is None:
            raise ValueError(
                "temperature > 0 samples from the model — pass rng=")
        if prefix_cache_bytes < 0:
            raise ValueError(
                f"prefix_cache_bytes must be >= 0 (0 disables the cache), "
                f"got {prefix_cache_bytes}")
        if kv_page_size < 0 or kv_pages < 0:
            raise ValueError(
                f"kv_page_size/kv_pages must be >= 0 (0 = dense layout), "
                f"got {kv_page_size}/{kv_pages}")
        if kv_pages and not kv_page_size:
            raise ValueError(
                "kv_pages sizes the PAGED pool — it needs kv_page_size > 0")
        if radix_cache and not kv_page_size:
            raise ValueError(
                "radix_cache shares whole KV PAGES between requests — it "
                "needs the paged cache (kv_page_size > 0)")
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = whole-prompt bucketed "
                f"prefill), got {prefill_chunk}")
        if prefill_chunk:
            if not kv_page_size:
                raise ValueError(
                    "prefill_chunk runs prompts through the paged suffix-"
                    "extend program — it needs the paged cache "
                    "(kv_page_size > 0)")
            if prefill_chunk > max_len:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) cannot exceed max_len "
                    f"({max_len}) — a chunk is at most one slot's span")
            if prefix_cache_bytes > 0:
                raise ValueError(
                    "prefill_chunk does not compose with the dense prefix "
                    "cache (prefix_cache_bytes > 0): chunked admission "
                    "never produces the bucketed row the cache stores — "
                    "the radix trie is the prefix-sharing mechanism under "
                    "chunking (radix_cache, on by default when paged)")
        if kv_page_size:
            if max_len % kv_page_size:
                raise ValueError(
                    f"max_len ({max_len}) must be a multiple of kv_page_size "
                    f"({kv_page_size}) so every slot's virtual span is "
                    "exactly max_len (the paged==dense parity contract)")
            if (getattr(model, "window", 0)
                    and not getattr(model, "has_window_rings", False)):
                raise ValueError(
                    "the paged cache does not compose with sliding-window "
                    "attention (model.window > 0) — the windowed decode "
                    "gathers a contiguous dense span (a model that keeps "
                    "its window layers in rings beside the pool says so: "
                    "has_window_rings)")
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if tp > 1:
            heads = getattr(model, "heads", 0)
            heads_kv = getattr(model, "heads_kv", None) or heads
            if not heads or heads % tp or heads_kv % tp:
                raise ValueError(
                    f"tp={tp} must divide heads ({heads}) and heads_kv "
                    f"({heads_kv}): the Megatron column/row split and the "
                    "KV head-axis shard both partition WHOLE heads — a "
                    "silent replicated degrade would void the 1/tp "
                    "per-chip memory claim")
        # --- context parallelism (ISSUE 20): sequence-sharded paged KV
        # over the cp axis of a 2-D cp×tp mesh, ring-attention prefill ---
        if cp < 1:
            raise ValueError(f"cp must be >= 1, got {cp}")
        if cp > 1:
            if not kv_page_size:
                raise ValueError(
                    "cp > 1 shards the PAGED KV pool along its page axis — "
                    "context-parallel serving needs the paged cache "
                    "(kv_page_size > 0); the dense per-slot layout has no "
                    "sequence axis a chip row could own")
            if max_len % cp:
                raise ValueError(
                    f"max_len ({max_len}) must be a multiple of cp ({cp}) "
                    "so every slot's virtual span splits into equal "
                    "per-chip-row sequence shards")
            if getattr(model, "attn_fn", None) is not None:
                raise ValueError(
                    "cp > 1 installs ring attention as the model's "
                    "attn_fn for prefill — a model that already carries a "
                    "custom attn_fn would be silently clobbered; pass the "
                    "base model and let the engine compose the ring")
        # --- layer kinds, resolved once: a model some of whose layers keep
        # a fixed-size recurrent state per row instead of per-token K/V
        # (models/sala.py) gets a state pool beside the page pool, owned by
        # the same cache tree and the same programs.  What such a model
        # cannot have yet is refused here, by name, never degraded.
        self._recurrent = bool(getattr(model, "has_recurrent_state", False))
        # which per-row leaves those are: a window layer's rings, of which
        # such a model's global layers alone own pages (models/mimo.py);
        # and whether some layers are a share of an expert layer, whose
        # load the device counts in a leaf the host reads back
        self._rings = bool(getattr(model, "has_window_rings", False))
        self._experts = bool(getattr(model, "has_expert_layers", False))
        # and how many of its layers run a state-space scan (models/
        # nemotron_h.py), for the counters of the scan and the update
        self._ssm_layers = int(getattr(model, "ssm_layers", 0))
        if self._recurrent:
            kind = ("window-ring and expert layers" if self._rings
                    else "recurrent-state layers")
            if not (kv_page_size and prefill_chunk):
                raise ValueError(
                    f"a model with {kind} is served through "
                    "the paged cache by chunked prefill only: it needs "
                    "kv_page_size > 0 and prefill_chunk > 0 (a chunk carries "
                    "the row's state forward; there is no bucketed prefill "
                    "for it)")
            if prefill_chunk % kv_page_size:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be whole pages "
                    f"({kv_page_size}) for a model with {kind}"
                    ": a chunk starts on a page and on a compressed-"
                    "key stride")
            if radix_cache:
                raise ValueError(
                    "radix prefix sharing is refused for a model with "
                    f"{kind}: a shared prefix's K/V pages are "
                    "only half of its cache — the state after that prefix "
                    "would have to be snapshotted and shared with them, and "
                    "this engine keeps no state snapshots (docs/SERVING.md); "
                    "pass radix_cache=False or leave it unset")
            radix_cache = False
            if (tp > 1 or cp > 1 or speculative is not None or role != "both"
                    or prefix_cache_bytes or quant == "int8"):
                raise ValueError(
                    f"a model with {kind} runs on one chip, "
                    "role='both', without speculative decoding, the prefix "
                    "cache or int8 weights: its paged kernels are not "
                    "partitioned, a rejected draft cannot rewind a state, a "
                    "handoff moves pages and not states"
                    + (", the expert banks have no int8 form and their "
                       "exchange across chips is not built" if self._experts
                       else ""))
        # persistent XLA compilation cache: warm processes (and respawned
        # replicas) skip recompiling the engine's program family.  Placed
        # from outside — utils/compile_cache.py
        enable_compile_cache()
        # --- weight-only int8 quantization (ISSUE 12) --- the model
        # clones to its Int8Dense form and the HOST param tree quantizes
        # ONCE here (per-output-channel symmetric scales, models/quant.py)
        # — BEFORE the tp mesh block below, so under tp=N the sharding
        # specs are computed over the QUANTIZED tree and the scale leaves
        # shard alongside the Megatron column/row splits (megatron_rule's
        # "scale" rule).  swap_params re-runs the same transform, so a
        # router hot-swap handing full-precision host checkpoints just
        # works.  The whole downstream program family (per-bucket prefill,
        # decode/verify windows, insert/reset, paged extend, prewarm) is
        # quant-blind: quant lives in the model fields + the param tree,
        # so the family stays one program per (site, shape-key).
        if quant not in (None, "none", "int8"):
            raise ValueError(
                f"quant must be None/'none' or 'int8' (weight-only int8 "
                f"matmuls with fused dequant), got {quant!r}")
        self.quant = "int8" if quant == "int8" else "none"
        if self.quant == "int8":
            try:
                model = model.clone(quant="int8")
            except TypeError:
                raise ValueError(
                    f"quant='int8' needs a model with a quant= field "
                    f"(the causal-LM family); {type(model).__name__} has "
                    "none") from None
            params = quantize_params_int8(params)
        # --- tensor/context-parallel mesh (tp=cp=1: no mesh, the same
        # programs on one chip — see the else branch) --- the
        # serving half of ROADMAP item 5b: weights column/row-sharded by
        # the SAME Megatron rule the training mesh uses, KV cache sharded
        # over the head axis, one psum per attention block and one per MLP
        # inserted by the partitioner at the column->row boundaries.  With
        # cp > 1 (ROADMAP item 2, ISSUE 20) the mesh grows a leading
        # ``cp`` axis: params REPLICATE over it (megatron_rule names only
        # "tp"), the paged pool shards its page axis over it
        # (kv_cache_rule cp=), and prefill runs ring attention along it.
        # Everything host-side (scheduler, pool, radix trie, drafter)
        # never sees the mesh — allocation decisions are identical at any
        # (cp, tp).
        self.tp = int(tp)
        self.cp = int(cp)
        if tp > 1 or cp > 1:
            mesh_devices = cp_devices if cp_devices is not None else tp_devices
            self._mesh = serving_mesh(tp, mesh_devices, cp=cp)
            self._kv_rule = kv_cache_rule(tp, axis="tp", cp=cp)
            self._param_shardings = mesh_shardings(
                self._mesh,
                make_param_specs(params, megatron_rule(tp, axis="tp")))
            # accepts a host or single-chip tree and re-shards wholesale —
            # the same seam swap_params reuses for hot-swap under tp
            params = jax.device_put(params, self._param_shardings)
            self._rep = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())
        else:
            # one chip.  The engine COMMITS its params, cache and uploads
            # to that chip, as the mesh path commits to its mesh: jit keys
            # its executables on which inputs are committed, so params that
            # arrive committed (from_trainer's device_put, a restored
            # checkpoint) beside uncommitted uploads would compile a second
            # program per site the first time a device-resident input
            # replaces an uploaded one — after prewarm(), on a request.
            self._mesh = None
            self._kv_rule = None
            self._rep = jax.sharding.SingleDeviceSharding(_home_device(params))
            self._param_shardings = self._rep
            params = jax.device_put(params, self._rep)
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.decode_ahead = int(decode_ahead)
        self.speculative = speculative
        self.draft_len = int(draft_len) if speculative is not None else 0
        # host-side prompt-lookup drafter (serving/drafter.py): pure numpy
        # suffix match over each request's prompt + generated tokens
        self._drafter = (
            NgramDrafter(self.draft_len) if speculative == "ngram" else None)
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.clock = clock
        # `is None`, NOT `or`: FIFOScheduler defines __len__, so an EMPTY
        # custom scheduler is falsy and `scheduler or default` would
        # silently discard it (with its buckets/bounds/clock)
        self._prefill_chunk = int(prefill_chunk)
        if scheduler is None:
            scheduler = FIFOScheduler(
                max_len=max_len,
                buckets=buckets if buckets is not None else
                tuple(b for b in (16, 32, 64, 128) if b <= max_len) or (max_len,),
                clock=clock, tracer=tracer,
                chunked_prefill=bool(prefill_chunk))
        elif buckets is not None:
            # the compiled prefill shapes are derived from the SCHEDULER's
            # buckets (one source of truth) — an engine-level buckets= that
            # disagrees is the drift bug this check exists to catch, not a
            # preference to silently resolve
            want = tuple(sorted(set(int(b) for b in buckets)))
            if want != scheduler.buckets:
                raise ValueError(
                    f"engine buckets= {want} != scheduler buckets "
                    f"{scheduler.buckets} — the prefill programs compile at "
                    "the scheduler's shapes, so a mismatch would admit "
                    "prompts the engine never compiled for")
        # chunking lifts the bucket bound at SUBMIT (scheduler) and honors
        # it at ADMISSION (engine) — the two sides must agree, like buckets
        if getattr(scheduler, "chunked_prefill", False) and not prefill_chunk:
            raise ValueError(
                "scheduler.chunked_prefill is set but the engine has no "
                "prefill_chunk= — the scheduler would admit prompts past "
                "the largest bucket that the engine cannot prefill")
        if prefill_chunk:
            scheduler.chunked_prefill = True
        self.scheduler = scheduler
        # ONE tracer serves a request's whole span tree: the scheduler
        # opens it (submit/queue), the engine continues it (admit/decode/
        # retire) — two different tracers would strand half-open trees in
        # each, so adopt whichever side has one and reject a conflict.
        sched_tracer = getattr(self.scheduler, "tracer", None)
        if tracer is None:
            tracer = sched_tracer
        elif sched_tracer is None:
            self.scheduler.tracer = tracer
        elif sched_tracer is not tracer:
            raise ValueError(
                "engine tracer= and scheduler.tracer are different Tracer "
                "objects — a request's span tree would be split across two "
                "buffers; wire ONE tracer (either side) and both will use it")
        self._tracer = tracer  # nil-guarded at every touch, like chaos
        # the engine's host-loop track.  0 (the default "host" track) for a
        # standalone engine; a Router gives each replica its own track
        # (tracer.track("replica <i>")) so N engine loops sharing ONE
        # tracer render as N lanes instead of interleaving on lane 0.
        self._trace_tid = int(trace_tid)
        # Compile accounting is always on (the listener is process-global
        # and costs nothing between compiles): the delta between this
        # baseline and shutdown is the engine's own program family, folded
        # into ServingStats as n_compiled_programs / compile_time_s.
        self._compile = CompileTracker.install()
        self._compile0 = self._compile.snapshot()
        if tracer is not None:
            self._compile.bind(tracer)
        if self.scheduler.max_len != max_len:
            raise ValueError(
                f"scheduler.max_len ({self.scheduler.max_len}) != engine "
                f"max_len ({max_len}) — admission would pass requests the "
                "cache cannot hold")
        self.buckets = self.scheduler.buckets
        self.writer = writer
        # disaggregated serving (ISSUE 16): "both" is the monolithic
        # engine, byte-identical to every prior PR.  "prefill" runs the
        # prefill/extend program family only and diverts finished
        # landings to a handoff outbox (serving/kv_handoff.py) instead of
        # decoding; "decode" accepts handed-off pages via
        # admit_prefilled() and never compiles a prefill bucket.
        self.role = role
        self.stats = ServingStats(slots, decode_ahead=self.decode_ahead,
                                  role=role)
        # prefill-role outbox: HandoffPacket per finished prefill, drained
        # by the router's handoff pump (or the owner directly in tests)
        self._outbox: deque = deque()
        self.handoffs_out = 0   # packets packaged (prefill side)
        self.handoffs_in = 0    # packets landed (decode side)

        # --- compiled device programs (all resident, all fixed-shape) ---
        # The engine's slot cache is DONATED through every program that
        # threads it (step/insert/reset): without donation XLA must copy
        # the whole (slots, max_len) cache per call to keep the input
        # buffer alive — measured ~23% of the dim-320 step on CPU.  Safe
        # because the engine immediately reassigns self.cache and never
        # touches the donated buffer again; the PUBLIC make_decode_step
        # stays undonated (callers own their caches).
        # paged mode decodes through the page pool: the DECODE-side
        # programs (window, insert, reset, extend) switch to the paged
        # layout while the prefill program family stays byte-identical
        # (prefill never touches the cache — core/generate.make_prefill)
        self._page_size = int(kv_page_size)
        if kv_page_size:
            n_row = max_len // kv_page_size
            if not kv_pages:
                # default: dense-equivalent capacity (+ the trash page) —
                # overcommit is opt-in via an explicit smaller kv_pages.
                # Under cp the pool's page axis shards cp ways, so the
                # default rounds UP to the next multiple of cp (a few
                # bonus pages, never fewer than dense-equivalent).
                kv_pages = slots * n_row + 1
                if self.cp > 1 and kv_pages % self.cp:
                    kv_pages += self.cp - kv_pages % self.cp
            elif self.cp > 1 and kv_pages % self.cp:
                raise ValueError(
                    f"kv_pages ({kv_pages}) must be a multiple of cp "
                    f"({self.cp}): the pool's page axis shards evenly "
                    "across the cp rows, or the 1/cp per-chip memory "
                    "claim silently degrades to replicated")
            if kv_pages < n_row + 1:
                raise ValueError(
                    f"kv_pages ({kv_pages}) cannot hold one full-length "
                    f"request: need >= max_len/kv_page_size + 1 "
                    f"({n_row + 1}; page 0 is the reserved trash page)")
            # the decode clone also learns the one fact about the mesh a
            # module cannot see: off-mesh (tp == 1 and cp == 1) the window
            # runs on one device, where single-token paged attention may be
            # a Mosaic kernel (models/transformer._paged_decode_attention)
            decode_model = model.clone(page_size=kv_page_size,
                                       paged_one_device=self._mesh is None)
        else:
            decode_model = model
        self._kv_pages = int(kv_pages)
        # what a decode step reads, by the model's own arithmetic: asked of
        # the clone, which knows the page size
        self._read_plan = getattr(decode_model, "decode_read_plan", None)

        # every jitted program that RETURNS a cache pins the KV layout at
        # its output (identity at tp=1): GSPMD propagation from the
        # committed sharded inputs would usually land there anyway, but the
        # pin makes the head-axis layout an explicit program invariant —
        # every program's cache OUTPUT is layout-identical to every
        # program's cache INPUT, which is what keeps donation legal and
        # the compile census at ONE program per (site, shape-key) under tp
        if self._mesh is not None:
            def _pin(tree):
                return jax.lax.with_sharding_constraint(
                    tree, mesh_shardings(
                        self._mesh, make_param_specs(tree, self._kv_rule)))
        else:
            def _pin(tree):
                return tree
        self._pin_kv = _pin

        # cp > 1 promotes ring attention from the training path into the
        # prefill program family (ISSUE 20): the prefill model's forward
        # runs attention as a shard_map island over the mesh's cp axis
        # (sequence-sharded K/V rotating via ppermute, GQA kept grouped at
        # H_kv width) with heads still sharded over tp.  Decode-mode
        # programs never consult attn_fn (the paged gather-based decode
        # attention reads the SEQUENCE-sharded pool and the partitioner
        # derives the cross-row collectives), so only the prefill family
        # changes.  Buckets that don't divide cp fall back to the
        # numerically-equivalent unsharded path inside the returned
        # callable — still one program per (site, shape-key).
        if self.cp > 1:
            ring = make_ring_attention(
                self._mesh, batch_axis=None, seq_axis="cp",
                head_axis="tp" if tp > 1 else None,
                causal=bool(getattr(model, "causal", True)))
            try:
                prefill_model = model.clone(attn_fn=ring)
            except TypeError:
                raise ValueError(
                    f"cp={cp} needs a model with an attn_fn= field (the "
                    f"causal-LM family); {type(model).__name__} has none"
                ) from None
        elif (tp > 1 and getattr(model, "attn", None) == "flash"
              and getattr(model, "attn_fn", None) is None):
            # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
            # shard_map" — first seen on the four-chip v5e host, PR 21; the
            # Pallas interpreter lowers to plain HLO, so the CPU mesh never
            # showed it).  Attention is head-parallel: the flash prefill
            # kernel runs as a shard_map island over tp, each chip on its
            # own H/tp query heads and H_kv/tp K/V heads (whole GQA groups
            # — tp divides both, checked above).
            heads = jax.sharding.PartitionSpec(None, None, "tp", None)
            prefill_model = model.clone(attn_fn=shard_map_compat(
                functools.partial(
                    flash_attention, causal=bool(getattr(model, "causal", True)),
                    window=int(getattr(model, "window", 0))),
                self._mesh, in_specs=(heads, heads, heads), out_specs=heads))
        else:
            prefill_model = model
        self._prefill = make_prefill(prefill_model, max_len)  # per-bucket shapes
        if kv_page_size:
            _insert_fn = make_paged_insert(kv_page_size, max_len)
            _reset_fn = paged_reset
        else:
            _insert_fn = self._insert_impl
            _reset_fn = reset_cache_slots

        # named functions, not lambdas: a program is found in a device
        # trace by its function's name (jit__insert_row, jit__reset_rows)
        def _insert_row(cache, *a):
            return _pin(_insert_fn(cache, *a))

        def _reset_rows(cache, mask):
            return _pin(_reset_fn(cache, mask))

        self._insert = jax.jit(_insert_row, donate_argnums=(0,))
        self._reset = jax.jit(_reset_rows, donate_argnums=(0,))

        pad_id_ = self.pad_id
        top_k_ = int(top_k)
        window_ = self.decode_ahead

        recurrent_ = self._recurrent

        def _window_impl(params, cache, tok, active, temps, topps, topks,
                         minps, keys, pos):
            # decode_ahead fused decode+pick steps as ONE dispatch
            # (core/generate.py _sample_window_core): the host loop pays
            # per-iteration dispatch latency and ONE blocking readback per
            # WINDOW instead of per token.  temperature/top_p/top_k/min_p/
            # base-key/position ride as per-slot DATA planes, so every
            # sampling mix (greedy included) is this ONE program — the
            # census never moves across distinct (temperature, top_p,
            # top_k, min_p, seed) configs.  What a step COMPUTES follows
            # the planes (lax.cond inside the program): no decoding row
            # samples -> argmax and the logprob only; some do -> the
            # filters and the draw, with the one [slots, vocab] sort only
            # if a sampled row has top-k or top-p on.
            if recurrent_:
                # (trace time: a uniform model's program never sees this)
                # the window is told which rows are decoding: a state layer
                # must not absorb the garbage step of an idle or prefilling
                # row (a K/V layer's lands in the trash page or above the
                # chunk cursor)
                n_valid = jnp.asarray(active, jnp.int32)
                cache = {name: {**e, "n_valid": n_valid}
                         for name, e in cache.items()}
            cache, blk, logps, last, pos = _sample_window_core(
                decode_model, params, cache, tok, active, temps, topps,
                topks, minps, keys, pos, window_, max_len, True, pad_id_)
            if recurrent_:
                cache = {name: {k: v for k, v in e.items() if k != "n_valid"}
                         for name, e in cache.items()}
            return _pin(cache), blk, logps, last, pos

        self._window = jax.jit(_window_impl, donate_argnums=(1,))

        if speculative is not None:
            # the speculative sibling: ONE (slots, draft_len+1)-position
            # target forward that verifies a host-drafted chunk, computes
            # per-slot acceptance in-graph (argmax match for greedy rows,
            # rejection sampling for sampled rows), and rewinds the KV
            # cursor to the acceptance point (core/generate.py
            # _verify_sample_core).  In spec mode this REPLACES the
            # decode-ahead scan as the per-window dispatch: drafting
            # happens on the host between windows, which a fused k-step
            # scan could never pause for.
            def _verify_impl(params, cache, chunk, draft_lens, active,
                             temps, topps, topks, minps, keys, pos):
                cache, *rest = _verify_sample_core(
                    decode_model, params, cache, chunk, draft_lens, active,
                    temps, topps, topks, minps, keys, pos, max_len, pad_id_)
                return (_pin(cache), *rest)

            self._verify = jax.jit(_verify_impl, donate_argnums=(1,))
        else:
            self._verify = None

        if kv_page_size:
            # partial-prefix prefill: compute only the unshared suffix of a
            # radix-matched prompt as one decode-mode chunk over the slot's
            # block table; the first-token pick runs separately through the
            # shared first_pick program (one pick program for every
            # landing path — miss, prefix hit, radix extend)
            _extend_impl = make_paged_extend(decode_model, max_len,
                                             kv_page_size)

            def _extend_row(params, cache, slot, bt_row, suffix,
                            start, suffix_len):
                cache, last = _extend_impl(params, cache, slot, bt_row,
                                           suffix, start, suffix_len)
                return _pin(cache), last

            self._extend = jax.jit(_extend_row, donate_argnums=(1,))

            # disaggregated handoff programs (serving/kv_handoff.py): one
            # fixed-shape page gather (read-only — the source pool stays
            # live until the transfer commits) and the destination-side
            # per-page scatter + no-forward block-table install, both with
            # the cache donated like every other cache-threading program
            self._page_gather = jax.jit(gather_page)

            def _page_write(cache, payload, pid):
                return _pin(page_write(cache, payload, pid))

            def _bt_install(cache, bt_row, slot, cur):
                return _pin(bt_install(cache, bt_row, slot, cur))

            self._page_write = jax.jit(_page_write, donate_argnums=(0,))
            self._bt_install = jax.jit(_bt_install, donate_argnums=(0,))

        def _prefill_row(params, prompt, lens):
            # the B=1 row cache is pinned head-sharded too: the insert
            # program's row input then always arrives in ONE layout,
            # whether it came from a fresh prefill, the prefix cache, or
            # prewarm's zero row.  Returns the (1, V) last-position logits
            # UNPICKED — the prefix cache stores them (never a sampled
            # token) and every admission picks through first_pick.
            cache, last = self._prefill(params, prompt, lens)
            return _pin(cache), last

        self._prefill_row = jax.jit(_prefill_row)
        # per-request sampling defaults: the engine-level knobs cover every
        # request submitted without SamplingParams.  The default base key
        # comes from the rng= knob's key data (host bytes — greedy engines
        # never touch it).
        self._default_temp = float(temperature)
        self._default_topp = float(top_p)
        self._top_k = top_k_
        self._default_minp = float(min_p)
        if rng is None:
            self._default_key = base_key(0)
        else:
            try:
                kd = jax.random.key_data(rng)
            except TypeError:
                kd = rng
            self._default_key = np.asarray(kd, np.uint32).reshape(-1)[-2:]

        # --- mutable engine state ---
        # cache zeros materialize DIRECTLY in their final layout: under tp
        # the shape probe runs first, the head-axis sharding tree is built
        # from it, and allocation jits with out_shardings — a pool bigger
        # than one chip's memory never transits a single device
        _shapes = (
            paged_cache_shapes(model, params, slots, max_len, kv_page_size,
                               kv_pages) if kv_page_size
            else cache_shapes(model, params, slots, max_len))
        self._cache_shardings = (
            self._rep if self._mesh is None else mesh_shardings(
                self._mesh, make_param_specs(_shapes, self._kv_rule)))
        if kv_page_size:
            self.cache = _zeros_like_shapes(_shapes, self._cache_shardings)
            self._pool = KVPagePool(kv_pages, kv_page_size)
            self._page_bytes = pool_page_bytes(self.cache)
            # whether the decode window's attention is the paged kernel:
            # the model's own rule (same predicate, same facts), evaluated
            # once so ServingStats can count the windows that took it
            _leaf = next(e["pages_k"] for e in self.cache.values()
                         if "pages_k" in e)
            self._paged_kernel = bool(
                speculative is None and decode_model.paged_one_device
                and paged_kernel_eligible(decode_model.dtype, _leaf.dtype,
                                          kv_page_size, *_leaf.shape[2:]))
            self._radix = (
                RadixCache(kv_page_size)
                if (radix_cache is None or radix_cache) else None)
            # per-slot allocation record: [private page ids, held radix
            # nodes] — released at retirement, DEFERRED until the slot's
            # reset dispatch (its stale block table references the pages
            # until then; see _release_slot_alloc)
            self._slot_alloc: list[list | None] = [None] * slots
            self._deferred_free: list[list] = []
        else:
            self.cache = _zeros_like_shapes(_shapes, self._cache_shardings)
            self._pool = None
            self._paged_kernel = False
            self._radix = None
            self._slot_alloc = [None] * slots
            self._deferred_free = []
        self._slot_req: list[Request | None] = [None] * slots
        # chunked-prefill progress per slot (ISSUE 14): None for slots in
        # normal decode; a dict {"done", "total", "bt", "bt_dev", "last",
        # "t0"} while the slot is PREFILLING — occupied (its pages are
        # allocated, its request is resident) but EXCLUDED from the decode
        # window's active mask until the last chunk lands and the first
        # token is picked
        self._slot_prefill: list[dict | None] = [None] * slots
        self._slot_tok = np.full((slots,), self.pad_id, np.int32)
        self._tok_dev = None  # device copy of _slot_tok; None = stale
        self._active_dev = None  # device (slots,) bool mask; None = stale
        # per-slot sampling planes (host mirrors): temperature/top-p as
        # (slots,) float32, the Threefry base key as (slots, 2) uint32.
        # Uploaded once per occupancy change (_planes_dev, invalidated at
        # admission like _tok_dev/_active_dev — a retired slot's stale
        # plane rows are masked by `active`, so no invalidation there).
        self._slot_temp = np.full((slots,), self._default_temp, np.float32)
        self._slot_topp = np.full((slots,), self._default_topp, np.float32)
        self._slot_topk = np.full((slots,), self._top_k, np.int32)
        self._slot_minp = np.full((slots,), self._default_minp, np.float32)
        self._slot_key = np.tile(self._default_key, (slots, 1))
        # (temps, topps, topks, minps, keys) on device; None = stale
        self._planes_dev = None
        # (samples, sorts) of the windows dispatched on _active_dev and
        # _planes_dev as they stand: recomputed with them, never per window
        self._pick_work = (False, False)
        # device (slots,) int32 count of already-generated tokens per slot
        # — the PRNG position plane.  Plain windows return the advanced
        # plane (carried like _tok_dev); spec windows re-upload fresh each
        # dispatch (acceptance makes the advance data-dependent).
        self._pos_dev = None
        # prefill-overlap parking lot: (req, (row_cache, logits, hit))
        # tuples prefilled against an in-flight window, awaiting a slot
        self._pending: deque[tuple] = deque()
        # ids of parked requests whose landing STALLED on a dry page pool
        # (overcommit): close() must FAIL these terminally (engine_fault —
        # the engine gave up on work it had accepted) instead of the
        # plain "cancelled" an overlap-prefilled pending gets
        self._stalled_ids: set[int] = set()
        self._prefix = (
            PrefixCache(prefix_cache_bytes) if prefix_cache_bytes > 0
            else None)
        self.completed: list[Request] = []
        # --- failure isolation / shutdown state ---
        self.stall_timeout_s = stall_timeout_s
        self._chaos = chaos  # utils/chaos.FaultInjector | None (see module doc)
        self._last_progress_t: float | None = None  # watchdog anchor
        # the anchor above resets on a fatal fault (retry-after-fatal must
        # restart the stall countdown); this stamp never does — it is the
        # "when did this engine last make progress" heartbeat the health
        # sampler reports, frozen at its final value after a kill
        self._last_progress_ever: float | None = None
        # utils/telemetry.Telemetry | None — same nil-guard zero-cost-off
        # contract as _chaos/_tracer.  The engine registers a vitals
        # source under its trace track id (a Router's replicas get unique
        # tids, so a respawn REPLACES its predecessor's source) and calls
        # maybe_sample once per step — a clock read between samples.
        self._telemetry = telemetry
        if telemetry is not None:
            telemetry.register_source(f"engine{trace_tid}",
                                      self._telemetry_vitals)
        self._draining = False  # drain(): serve what's accepted, admit no more
        self._closed = False
        # per-chip footprint stamped up front: even a run that serves zero
        # requests reports what the config costs one chip (ISSUE 10)
        self._stamp_memory()

    def _telemetry_vitals(self) -> dict:
        """Health-sampler vitals (utils/telemetry): queue/slot/pool state
        plus the stats counters, all O(1) reads — safe every interval."""
        v = self.stats.vitals()
        v.update(
            queue_depth=len(self.scheduler),
            parked=len(self._pending),
            overcommit_stalled=len(self._stalled_ids),
            occupied_slots=self.occupied,
            slots=self.slots,
            draining=self._draining,
            closed=self._closed,
            last_progress_t=self._last_progress_t,
        )
        return v

    def _sample_row_leaves(self) -> None:
        """Occupancy of the per-row leaves beside the page pool (one row a
        slot): the recurrent-state pool's, or the window rings'."""
        sample = (self.stats.ring_sample if self._rings
                  else self.stats.state_sample)
        sample(self.occupied, self.slots)

    def _count_recurrent_window(self) -> None:
        """The counters of an engine whose model keeps per-row leaves
        beside its pages, taken where the decode window is dispatched: the
        rows' occupancy, the pairs the window routes over expert layers,
        the row-steps its state-space layers update, and what the window's
        attention reads — the MODEL's own arithmetic
        (``decode_read_plan``) on the host's record of each decoding row's
        length."""
        self._sample_row_leaves()
        ctx = np.array(
            [r.tokens.size + len(r.generated)
             for r, p in zip(self._slot_req, self._slot_prefill)
             if r is not None and p is None], np.int64)
        if self._experts:
            self.stats.expert_tokens(
                self.model.expert_pairs(ctx.size * self.decode_ahead))
        if self._ssm_layers:
            self.stats.ssm_step(ctx.size * self.decode_ahead * self._ssm_layers)
        if self._read_plan is None or not ctx.size:
            return
        # step i of the window writes the token at position ctx - 1 + i
        plan = self._read_plan(ctx[:, None] + np.arange(self.decode_ahead))
        if self._rings:
            self.stats.global_step(plan)
        else:
            self.stats.sparse_step(*plan)

    def sync_expert_load(self) -> None:
        """Read the device's expert-load counters into ``self.stats`` — one
        small transfer, for whoever wants the counters current (the emit
        points; a benchmark at its window's edges).  Never in the step
        loop: a step adds to the leaf on the device and moves nothing."""
        if not self._experts:
            return
        leaves = [e["expert_load"] for e in self.cache.values()
                  if "expert_load" in e]
        if any(leaf.is_deleted() for leaf in leaves):
            return  # a faulted dispatch took the donated cache with it
        self.stats.expert_load(jax.device_get(leaves))

    def _stamp_memory(self) -> None:
        """(Re-)stamp the per-chip memory figures into ``self.stats`` —
        at construction, and again at every drain/close emit point so a
        caller that swapped in a fresh ServingStats still reports them."""
        self.stats.memory(
            tp=self.tp, kv_bytes_per_chip=self.kv_bytes_per_chip(),
            weight_bytes_per_chip=self.weight_bytes_per_chip(),
            quant=self.quant, cp=self.cp)

    def _site(self, name: str) -> str:
        """Path-qualified compile-site name (ISSUE 20 satellite): cp=1
        engines keep every historical site name byte-identical; cp>1
        qualifies each site with the layout — ``prefill[b16]`` becomes
        ``prefill[b16,cp2]``, ``first_pick`` becomes ``first_pick[cp2]``
        — so a census diff between layouts attributes every compile to
        its (site, shape-key, LAYOUT) and prewarm/serving keys always
        agree (both come through this helper)."""
        if self.cp == 1:
            return name
        if name.endswith("]"):
            return f"{name[:-1]},cp{self.cp}]"
        return f"{name}[cp{self.cp}]"

    def _dev(self, x):
        """Host upload for per-window device inputs: COMMITTED, to the
        engine's chip or replicated on its mesh, so the first dispatch
        (prewarm) and every serving dispatch present jit the SAME input
        shardings — one program per site, never a layout-keyed recompile.
        One hop from host memory (no uncommitted intermediate)."""
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        return jax.device_put(x, self._rep)

    @property
    def _chip0(self):
        """The accounting chip: per-chip byte figures are measured on one
        fixed mesh device (they are equal across the mesh by symmetry)."""
        return None if self._mesh is None else self._mesh.devices.flat[0]

    def kv_bytes_per_chip(self) -> int:
        """KV-cache bytes resident on ONE chip — the whole cache at
        tp=cp=1; the head-axis shard under tp (1/tp of the slab bytes,
        the ISSUE 10 memory claim) and additionally the page-axis shard
        under cp (1/(tp*cp) of the slab — the ISSUE 20 claim), plus the
        replicated block tables/cursors (the documented tax)."""
        return per_chip_bytes(self.cache, self._chip0)

    def weight_bytes_per_chip(self) -> int:
        """Decode-weight bytes resident on ONE chip (Megatron column/row
        shards under tp; replicated leaves count whole)."""
        return per_chip_bytes(self.params, self._chip0)

    @staticmethod
    def _insert_impl(cache, row_cache, slot):
        """Write row 0 of a B=1 prefill cache into ``slot`` of the engine
        cache (every leaf is (B, ...)-leading, so one dynamic_update_slice
        per leaf; ``slot`` is traced — one compile covers every slot)."""
        return jax.tree.map(
            lambda full, row: jax.lax.dynamic_update_slice(
                full, row.astype(full.dtype),
                (slot,) + (0,) * (full.ndim - 1)),
            cache, row_cache)

    @classmethod
    def from_trainer(cls, trainer, *, slots: int, max_len: int, **kw
                     ) -> "InferenceEngine":
        """Build an engine from a trained :class:`~...core.trainer.Trainer`
        run: the same clean single-device decode model + device-resident
        cast params ``Trainer.generate`` uses (training islands dropped,
        pp-stacked params unstacked)."""
        from distributed_tensorflow_ibm_mnist_tpu.models import get_model, model_accepts

        if not model_accepts(trainer.config.model, "pos") or not trainer.causal:
            raise ValueError(
                "InferenceEngine needs a causally-trained causal-LM-family "
                f"run; got {trainer.config.model!r}")
        clean_kwargs = {
            k: v for k, v in trainer.config.model_kwargs.items()
            if k not in ("attn_fn", "moe_fn", "pipeline_fn", "pp_stages")
        }
        model = get_model(trainer.config.model,
                          num_classes=trainer.num_classes, **clean_kwargs)
        kw.setdefault("writer", trainer.writer)
        return cls(model, trainer._decode_params(), slots=slots,
                   max_len=max_len, **kw)

    # ------------------------------------------------------------------
    # request lifecycle

    def submit(self, prompt, max_new: int, deadline_s: float | None = None,
               callback: Callable | None = None,
               ttft_slo_s: float | None = None,
               tpot_slo_s: float | None = None,
               sampling: SamplingParams | None = None) -> Request:
        """Enqueue a request (see :meth:`FIFOScheduler.submit` for the
        admission rules; raises ``QueueFull`` under backpressure).
        ``callback(request, token)`` streams every generated token; if it
        raises, THIS request fails (terminal ``failed`` state) and the
        engine keeps serving the rest.  ``ttft_slo_s``/``tpot_slo_s``
        declare latency SLO targets the engine judges at first token and
        retirement (never cancels — accounting only; serving/stats.py).
        ``sampling`` is the per-request :class:`SamplingParams`
        (temperature/top_p/seed; None = the engine's construction
        defaults) — the request's token stream is a pure function of its
        seed.  Refused after :meth:`drain` / :meth:`close`."""
        if self._closed or self._draining:
            raise RuntimeError(
                "engine is " + ("closed" if self._closed else "draining")
                + " — no new requests")
        if self.role == "decode":
            raise RuntimeError(
                "decode-role engine takes no direct submissions — its work "
                "arrives prefilled via admit_prefilled (route admissions "
                "to a prefill/both replica; serving/router.py does)")
        return self.scheduler.submit(prompt, max_new, deadline_s=deadline_s,
                                     callback=callback,
                                     ttft_slo_s=ttft_slo_s,
                                     tpot_slo_s=tpot_slo_s,
                                     sampling=sampling)

    @property
    def occupied(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def _decoding(self) -> int:
        """Slots holding a request that is past prefill — PREFILLING
        slots are occupied (pages held, request resident) but excluded
        from the decode window until their last chunk lands."""
        return sum(r is not None and p is None
                   for r, p in zip(self._slot_req, self._slot_prefill))

    @property
    def has_work(self) -> bool:
        return (self.occupied > 0 or len(self.scheduler) > 0
                or len(self._pending) > 0)

    @property
    def heartbeat_t(self) -> float | None:
        """Monotonic timestamp of the engine's last real progress (a token
        produced), or None before the first.  The EXTERNAL liveness signal:
        ``stall_timeout_s`` is judged inside :meth:`step`, so a pump thread
        wedged mid-step can never trip it — the daemon's watchdog thread
        reads this instead and declares the replica dead when it freezes
        while work is in flight (serving/daemon.py, serving/replica.py)."""
        return self._last_progress_ever

    def _req_sampling(self, req: Request):
        """``(temperature, top_p, top_k, min_p, base_key)`` resolved for
        ``req`` — its own :class:`SamplingParams`, or the engine's
        construction-time defaults for requests submitted without one."""
        s = req.sampling
        if s is None:
            return (self._default_temp, self._default_topp, self._top_k,
                    self._default_minp, self._default_key)
        return (float(s.temperature), float(s.top_p), int(s.top_k),
                float(s.min_p), s.key())

    def _first_pick(self, req: Request, logits):
        """Pick ``req``'s FIRST token (generated index 0) from the
        prefill's (1, V) last-position logits through the module-level
        shared ``first_pick`` program (serving/sampling.py) — the same
        program for a fresh prefill, a prefix-cache hit, and a paged
        radix-extend landing, so hit/miss first tokens are bit-identical.
        Returns ``(token, logprob)`` as host scalars."""
        temp, topp, topk, minp, key = self._req_sampling(req)
        # the span covers the host read below too — the blocking wait for
        # the prefill's logits, which the first_pick SITE leaves outside
        with host_span("engine.first_pick", req=req.id):
            with self._compile.site(self._site("first_pick")):
                tok, logp = first_pick(
                    logits, self._dev(np.array([temp], np.float32)),
                    self._dev(np.array([topp], np.float32)),
                    self._dev(np.array([topk], np.int32)),
                    self._dev(np.array([minp], np.float32)),
                    self._dev(key[None, :].astype(np.uint32)),
                    self._dev(np.zeros((1,), np.int32)))
            return int(tok[0]), float(logp[0])

    # ------------------------------------------------------------------
    # tracing bookkeeping (every helper is a no-op without a tracer —
    # the same zero-cost-when-unwired contract as the chaos hooks)

    def _tr_phase(self, req: Request, name: str, **args) -> None:
        """Advance ``req`` to its next lifecycle phase: close the open
        phase span (queue/admit/decode) and open ``name`` in its place,
        parented under the request's root span."""
        if self._tracer is None or req.trace is None:
            return
        t = req.trace
        if t.get("phase") is not None:
            self._tracer.end(t["phase"])
        t["phase"] = self._tracer.begin(name, cat="serving", parent=t["id"],
                                        tid=t["tid"], **args)

    def _tr_instant(self, req: Request, name: str, **args) -> None:
        """A correlated event ON this request's tree (fault injections,
        cache hits, first token)."""
        if self._tracer is None or req.trace is None:
            return
        self._tracer.instant(name, cat="serving", parent=req.trace["id"],
                             tid=req.trace["tid"], **args)

    def _tr_close(self, req: Request, **args) -> None:
        """Terminal: close the open phase (if any) and the request root."""
        if self._tracer is None or req.trace is None:
            return
        t = req.trace
        if t.get("phase") is not None:
            self._tracer.end(t["phase"])
        self._tracer.end(t["id"], **args)
        req.trace = None

    def _retire(self, slot: int, status: str, now: float,
                waste: int = 0) -> None:
        # the freed slot's stale token keeps being fed to the decode step
        # (its output is ignored and its cache row is reset), so _slot_tok
        # needs no write here — which keeps _tok_dev valid across retires
        req = self._slot_req[slot]
        req.status = status
        req.finish_t = now
        # TPOT SLO verdict at retirement: mean seconds per output token
        # AFTER the first (the decode steady-state the SLO names).  A
        # single-token request has no inter-token interval — trivially ok.
        if req.tpot_slo_s is not None and status == "done":
            n = len(req.generated)
            if req.first_token_t is not None and n > 1:
                req.slo_tpot_ok = (
                    (now - req.first_token_t) / (n - 1) <= req.tpot_slo_s)
            else:
                req.slo_tpot_ok = True
        if self._telemetry is not None and status == "done":
            ex = (req.trace_ctx.trace_id
                  if req.trace_ctx is not None else None)
            self._telemetry.observe("latency_s", now - req.submit_t,
                                    exemplar=ex)
            n = len(req.generated)
            if req.first_token_t is not None and n > 1:
                self._telemetry.observe(
                    "tpot_s", (now - req.first_token_t) / (n - 1),
                    exemplar=ex)
        self._slot_req[slot] = None
        self._slot_prefill[slot] = None  # a PREFILLING slot can be swept
        self._release_slot_alloc(slot)  # paged: queue its pages for release
        self._active_dev = None  # occupancy changed; next window re-freezes
        self._tr_close(req, status=status, slot=slot, waste_steps=waste,
                       n_generated=len(req.generated))
        self.completed.append(req)
        self.stats.add(req)

    def _fail(self, req: Request, exc: BaseException, now: float) -> None:
        """Move ``req`` to the terminal FAILED state (isolated casualty)."""
        req.status = "failed"
        req.error = f"{type(exc).__name__}: {exc}"
        req.finish_t = now
        if self._tracer is not None and req.trace is not None:
            from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import ChaosFault

            if isinstance(exc, ChaosFault):
                # the injected fault lands ON the request it hit — the
                # site's event index correlates it back to the FaultPlan
                self._tr_instant(req, "chaos_fault", site=exc.site,
                                 fault_kind=exc.kind, event=exc.event)
            self._tr_close(req, status="failed", error=req.error)
        self.completed.append(req)
        self.stats.add(req)

    def _notify(self, req: Request, tok: int) -> None:
        """Deliver one token to the request's streaming callback.  Raises
        propagate to the caller, which fails THIS request only."""
        if self._chaos is not None:
            from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import ChaosFault

            self._chaos.raise_if_fired("serving-callback", ChaosFault)
        if req.callback is not None:
            req.callback(req, tok)

    def _prefill_request(self, req: Request):
        """The per-request half of admission: one ``serving-admit`` chaos
        event, a prefix-cache lookup, and (on a miss) the bucketed B=1
        prefill dispatch.  Returns ``(row_cache, logits, cache_hit)``;
        exceptions are the REQUEST's failure and propagate to the caller
        (inline admit or overlap dispatch), which fails it in isolation.
        The chaos event fires once per admission attempt, hit or miss, so
        per-site event indices are independent of the prefix cache and of
        WHEN (inline vs overlapped) the prefill ran."""
        if self._chaos is not None:
            from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import ChaosFault

            self._chaos.raise_if_fired("serving-admit", ChaosFault)
        if self._prefix is not None:
            hit = self._prefix.get(req.prefix_key)
            self.stats.prefix(hit is not None)
            if hit is not None:
                self._tr_instant(req, "prefix_cache_hit", bucket=req.bucket)
                return hit[0], hit[1], True
        if self._radix is not None and self._usable_radix_tokens(req) > 0:
            # partial-prefix hit: skip the prefill dispatch NOW; the
            # suffix-extend program runs at landing against the trie/pool
            # state of that moment (the match is re-taken there — eviction
            # may shrink it while the request is parked)
            return _RADIX_PREFILL, None, False
        return (*self._dense_prefill(req), False)

    def _dense_prefill(self, req: Request):
        """The bucketed B=1 prefill dispatch — the dense tail of
        :meth:`_prefill_request`, also the paged landing's fallback when a
        parked radix match was evicted before landing.  Returns
        ``(row_cache, logits)``: the first-token pick happens at LANDING
        through the shared ``first_pick`` program, never here — the
        logits are the deterministic product the prefix cache may store."""
        padded = np.full((1, req.bucket), self.pad_id, np.int32)
        padded[0, : req.tokens.size] = req.tokens
        span = (self._tracer.begin("prefill", cat="serving",
                                   parent=req.trace["phase"] or req.trace["id"],
                                   tid=req.trace["tid"], bucket=req.bucket)
                if self._tracer is not None and req.trace is not None else None)
        try:
            with self._compile.site(self._site(f"prefill[b{req.bucket}]")):
                row_cache, logits = self._prefill_row(
                    self.params, jnp.asarray(padded),
                    jnp.asarray([req.tokens.size], jnp.int32))
        finally:
            if span is not None:
                self._tracer.end(span)  # a poisoned prefill still closes it
        return row_cache, logits

    def _usable_radix_tokens(self, req: Request, matched: int | None = None
                             ) -> int:
        """Whole-page radix match length usable for ``req``, capped so at
        least ONE prompt token remains for the suffix (the extend program
        needs a real position to pick the first token from)."""
        if matched is None:
            _, matched = self._radix.match(req.tokens)
        ps = self._page_size
        return min(matched, ((int(req.tokens.size) - 1) // ps) * ps)

    def _alloc_pages(self, n: int) -> list[int] | None:
        """``n`` pool pages, evicting unreferenced radix leaves to cover a
        shortfall; None = genuinely dry (every page is held by a live slot
        or a referenced prefix) — an admission STALL, never a failure."""
        pages = self._pool.alloc(n)
        if pages is None and self._radix is not None:
            self._radix.evict(n - self._pool.free_count,
                              lambda p: self._pool.free([p]))
            pages = self._pool.alloc(n)
        return pages

    def _release_slot_alloc(self, slot: int) -> None:
        """Queue ``slot``'s page allocation for release.  DEFERRED, not
        immediate: the slot's stale block table still references the pages
        until its reset dispatch lands, so the free (and any radix release
        that makes nodes evictable) only happens at _flush_freed_pages,
        called after the step's reset went out."""
        alloc = self._slot_alloc[slot]
        if alloc is not None:
            self._slot_alloc[slot] = None
            self._deferred_free.append(alloc)

    def _flush_freed_pages(self) -> None:
        """Apply deferred page frees / radix releases (see above)."""
        if self._pool is None or not self._deferred_free:
            return
        for pages, nodes in self._deferred_free:
            if pages:
                self._pool.free(pages)
            if nodes:
                self._radix.release(nodes)
        self._deferred_free.clear()

    def _paged_land(self, req: Request, slot: int, prefilled: tuple):
        """Land ``req`` in ``slot`` on the PAGED layout: allocate its page
        span, install the block table, and either write the pages under
        the dense prefill row's cursor into the pool (full prefill /
        prefix-cache hit) or run the suffix-extend program over the
        radix-shared prefix.  Returns ``(first_token, first_logprob,
        cache_hit)`` or None when the pool cannot cover the request right
        now (the caller re-parks it — admission stall, not failure)."""
        row_cache, logits, cache_hit = prefilled
        ps = self._page_size
        n_tok = int(req.tokens.size)
        path: list = []
        m_tok = 0
        if row_cache is _RADIX_PREFILL:
            # re-match at landing: the parked match may have been evicted
            # (or grown) while the request waited for a slot
            path, matched = self._radix.match(req.tokens)
            m_tok = self._usable_radix_tokens(req, matched)
            path = path[: m_tok // ps]
            if not path:
                # evaporated: plain dense prefill, WITHOUT re-firing the
                # serving-admit chaos event (it fired at _prefill_request —
                # one event per admission attempt, paging-invariant)
                row_cache, logits = self._dense_prefill(req)
                m_tok = 0
        m_blocks = len(path)
        if m_blocks:
            # pin the matched pages before any allocation could evict them
            self._radix.acquire(path)
        total = pages_needed(n_tok + req.max_new, ps)
        private = self._alloc_pages(total - m_blocks)
        if private is None:
            if m_blocks:
                self._radix.release(path)
            return None
        # record the allocation BEFORE any dispatch: if the extend/insert
        # (or the first-token callback downstream) raises, the failure
        # path's _release_slot_alloc reclaims these pages
        self._slot_alloc[slot] = [list(private), list(path)]
        bt_row = np.zeros((self.max_len // ps,), np.int32)  # rest = TRASH
        for j, node in enumerate(path):
            bt_row[j] = node.page
        for j, page in enumerate(private):
            bt_row[m_blocks + j] = page
        bt_dev = self._dev(bt_row)
        if m_blocks:
            suffix = req.tokens[m_tok:]
            sb = self.scheduler.bucket_for(suffix.size)
            padded = np.full((1, sb), self.pad_id, np.int32)
            padded[0, : suffix.size] = suffix
            with self._compile.site(self._site(f"extend[b{sb}]")):
                self.cache, ext_logits = self._extend(
                    self.params, self.cache, jnp.asarray(slot, jnp.int32),
                    bt_dev, jnp.asarray(padded),
                    jnp.asarray(m_tok, jnp.int32),
                    jnp.asarray(suffix.size, jnp.int32))
            if self.role == "prefill":
                # disaggregated (ISSUE 16): stop where the pick would
                # run — the logits row travels in the packet and the
                # DECODE side picks through the same shared program
                first, first_logp, land_logits = _HANDOFF, None, ext_logits
            else:
                first, first_logp = self._first_pick(req, ext_logits)
            self.stats.radix(True, tokens=m_tok)
            self._radix.record(True, tokens=m_tok)
            req.radix_tokens = m_tok
            self._tr_instant(req, "radix_hit", blocks=m_blocks, tokens=m_tok)
        else:
            with self._compile.site(self._site("slot_insert")):
                self.cache = self._insert(self.cache, row_cache, bt_dev,
                                          jnp.asarray(slot, jnp.int32))
            self.stats.inserted(pages_needed(n_tok, ps))
            if self.role == "prefill":
                first, first_logp, land_logits = _HANDOFF, None, logits
            else:
                first, first_logp = self._first_pick(req, logits)
            if self._radix is not None:
                self.stats.radix(False)
                self._radix.record(False)
            if self._prefix is not None and not cache_hit:
                # store the DETERMINISTIC prefill products only (row +
                # logits), never the picked token — sampling safety
                self._prefix.put(req.prefix_key, row_cache, logits)
        req.pages = total
        if self._radix is not None:
            # donate the freshly computed FULL prompt blocks below the
            # match: they move from this request's private allocation into
            # the trie (held — ref stays up until this slot retires)
            donate = {j: int(bt_row[j])
                      for j in range(m_blocks, n_tok // ps)}
            if donate:
                priv, nodes = self._slot_alloc[slot]
                held, _kept = self._radix.insert(
                    req.tokens, m_blocks, donate, path)
                for node in held:
                    priv.remove(node.page)
                    nodes.append(node)
        if first is _HANDOFF:
            # package AFTER the donation, so the source trie shares this
            # prompt's blocks with later prefills (and with the re-prefill
            # a dead transfer falls back to); exceptions propagate to
            # _admit's failure path, which reclaims the still-slot-held
            # allocation
            self._handoff_package(req, slot, land_logits, bt_row)
        return first, first_logp, cache_hit

    def _admit(self, req: Request, slot: int, now: float,
               prefilled: tuple | None = None) -> bool:
        """Prefill ``req`` at its bucket shape and land it in ``slot``
        (``prefilled`` carries an overlap-dispatched prefill to land
        instead of prefilling inline).

        Failure-isolated: any exception from the request's OWN processing
        (prefill, first-token callback, injected ``serving-admit`` poison)
        fails the request and leaves the slot free.  Returns True when the
        slot's cache row needs a reset the caller must perform unless a
        later admit overwrites it: a failure AFTER the insert landed, or a
        request that retired at admission (its prefilled row would
        otherwise linger under an idle slot).
        """
        if self._prefill_chunk:
            # chunked admission (ISSUE 14): allocate the page span and
            # park the slot in the PREFILLING state — chunks run one per
            # engine iteration, never a whole-prompt prefill here
            return self._chunk_admit(req, slot, now,
                                     retry=prefilled is not None)
        inserted = False
        # inline admissions open their "admit" phase here; overlap-prefilled
        # requests opened it back at pop (in _overlap_prefill), so their
        # phase also covers the prefill and the parked wait for a slot
        if req.trace is not None and req.trace.get("phase") is None:
            self._tr_phase(req, "admit", slot=slot)
        try:
            if prefilled is None:
                prefilled = self._prefill_request(req)
            if self._pool is not None:
                with host_span("engine.land", req=req.id) as land:
                    landed = self._paged_land(req, slot, prefilled)
                    if landed is not None:
                        land.set_metadata(
                            pages=req.pages,
                            radix_blocks=req.radix_tokens // self._page_size)
                if landed is None:
                    # pool momentarily full — NOT a failure: the caller
                    # re-parks the (already chaos'd, maybe prefilled)
                    # request and retries once decode frees pages
                    return ("stall", prefilled)
                first, first_logp, cache_hit = landed
                inserted = True
                if first is _HANDOFF:
                    # prefill role: the landing went to the outbox, the
                    # slot is free again (pages moved to the packet's
                    # hold) — True asks the caller to reset the row's
                    # block table unless a later admit overwrites it
                    return True
            else:
                row_cache, logits, cache_hit = prefilled
                with self._compile.site(self._site("slot_insert")):
                    self.cache = self._insert(
                        self.cache, row_cache, jnp.asarray(slot, jnp.int32))
                inserted = True
                # hit or miss, the pick runs HERE, per request, through the
                # one shared first_pick program — what makes the prefix
                # cache sampling-safe (it stores logits, never a token)
                first, first_logp = self._first_pick(req, logits)
                if self._prefix is not None and not cache_hit:
                    # insert does not donate row_cache, so the row stays
                    # valid to replay for every later identical prompt
                    self._prefix.put(req.prefix_key, row_cache, logits)
            req.admit_t = now
            req.generated.append(first)
            req.logprobs.append(first_logp)
            req.first_token_t = self.clock()  # TTFT: first token ON THE HOST
            # first token = progress: stamp the heartbeat here too, so an
            # engine killed later in this same step (before the end-of-step
            # stamp) still freezes at a real progress time, not None
            self._last_progress_ever = req.first_token_t
            # TTFT SLO verdict lands HERE, at the judgment point itself —
            # queue wait is inside TTFT by construction (stats docstring)
            if req.ttft_slo_s is not None:
                req.slo_ttft_ok = (
                    req.first_token_t - req.submit_t <= req.ttft_slo_s)
            if self._telemetry is not None:
                self._telemetry.observe(
                    "ttft_s", req.first_token_t - req.submit_t,
                    exemplar=(req.trace_ctx.trace_id
                              if req.trace_ctx is not None else None))
                # step()'s `produced` counts decode-window tokens only;
                # the admit-time first token lands here so the registry
                # counter matches stats' tokens_generated
                self._telemetry.inc("tokens_generated")
            req.status = "running"
            self._tr_instant(req, "first_token", slot=slot,
                             cache_hit=cache_hit)
            self._notify(req, first)
        except Exception as e:
            # a paged landing that allocated before raising gives its
            # pages back (deferred past the caller's reset dispatch)
            self._release_slot_alloc(slot)
            self._fail(req, e, self.clock())
            return inserted
        self._slot_req[slot] = req
        self._slot_tok[slot] = first
        temp, topp, topk, minp, key = self._req_sampling(req)
        self._slot_temp[slot] = temp
        self._slot_topp[slot] = topp
        self._slot_topk[slot] = topk
        self._slot_minp[slot] = minp
        self._slot_key[slot] = key
        self._tok_dev = None  # host mirror changed; re-upload before decode
        self._active_dev = None
        self._planes_dev = None  # sampling planes changed with the slot
        self._pos_dev = None  # rebuilt from host generated counts
        self._tr_phase(req, "decode", slot=slot)
        if self._done_reason(req) is not None:
            self._retire(slot, self._done_reason(req), self.clock())
            return True  # the landed row belongs to no live request now
        return False

    def _done_reason(self, req: Request) -> str | None:
        if self.eos_id is not None and req.generated and req.generated[-1] == self.eos_id:
            return "done"
        if len(req.generated) >= req.max_new:
            return "done"
        return None

    # ------------------------------------------------------------------
    # chunked prefill (ISSUE 14): admission holds a slot in the
    # PREFILLING state while fixed-size prompt chunks run one per engine
    # iteration through the paged suffix-extend program — the decode
    # latency cost of admitting ANY prompt is bounded by one chunk

    def _chunk_admit(self, req: Request, slot: int, now: float,
                     retry: bool = False):
        """Admit ``req`` into ``slot`` in the PREFILLING state: fire the
        one ``serving-admit`` chaos event (skipped on a stall ``retry`` —
        one event per admission ATTEMPT, exactly like the whole-prompt
        path), take the radix match (a partial hit resumes chunking at
        the divergence page), allocate the full page span, and build the
        host-side chunk record.  No chunk is dispatched here — the first
        runs at the next :meth:`_chunk_tick`.  Returns the same protocol
        as :meth:`_admit`: ``("stall", _CHUNK_STALL)`` when the pool is
        momentarily dry (caller re-parks), True/False for
        needs-reset-without-occupancy, with ``self._slot_req[slot]`` set
        on success.

        The slot's block table is NOT installed here: a reset pending
        from the previous tenant stays pending (garbage decode writes
        land in the trash page), and every chunk's extend call installs
        the real block table itself before writing."""
        if req.trace is not None and req.trace.get("phase") is None:
            self._tr_phase(req, "admit", slot=slot, chunked=True)
        try:
            if not retry and self._chaos is not None:
                from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
                    ChaosFault,
                )

                self._chaos.raise_if_fired("serving-admit", ChaosFault)
        except Exception as e:
            self._fail(req, e, self.clock())
            return False
        ps = self._page_size
        n_tok = int(req.tokens.size)
        path: list = []
        m_tok = 0
        if self._radix is not None:
            path, matched = self._radix.match(req.tokens)
            m_tok = self._usable_radix_tokens(req, matched)
            path = path[: m_tok // ps]
            m_tok = len(path) * ps
        m_blocks = len(path)
        if m_blocks:
            # pin the matched pages before any allocation could evict them
            self._radix.acquire(path)
        total = pages_needed(n_tok + req.max_new, ps)
        private = self._alloc_pages(total - m_blocks)
        if private is None:
            if m_blocks:
                self._radix.release(path)
            return ("stall", _CHUNK_STALL)
        self._slot_alloc[slot] = [list(private), list(path)]
        bt_row = np.zeros((self.max_len // ps,), np.int32)  # rest = TRASH
        for j, node in enumerate(path):
            bt_row[j] = node.page
        for j, page in enumerate(private):
            bt_row[m_blocks + j] = page
        req.pages = total
        req.admit_t = now
        req.status = "prefilling"
        self._slot_req[slot] = req
        self._slot_prefill[slot] = {
            "done": m_tok, "m_blocks": m_blocks, "path": path,
            "bt": bt_row, "bt_dev": self._dev(bt_row), "last": None,
            "t0": now,
        }
        self._active_dev = None  # occupancy changed; the slot joins the
        # window INACTIVE until its last chunk lands and first_pick runs
        if m_blocks:
            self.stats.radix(True, tokens=m_tok)
            self._radix.record(True, tokens=m_tok)
            req.radix_tokens = m_tok
            self._tr_instant(req, "radix_hit", blocks=m_blocks,
                             tokens=m_tok)
        elif self._radix is not None:
            self.stats.radix(False)
            self._radix.record(False)
        self.stats.prompt_admitted(n_tok)
        return False

    def _chunk_tick(self, reset_mask) -> bool:
        """Dispatch ONE prefill chunk — the chunked-prefill sibling of
        :meth:`_overlap_prefill`, called at the same seam (between the
        window dispatch and its blocking readback) so the chunk's compute
        hides behind the in-flight window; also called when no window
        dispatched (nothing decoding) so prefill still progresses.  One
        chunk per engine iteration TOTAL bounds every co-resident
        request's added decode latency at one chunk.  Picks the oldest
        PREFILLING slot (FIFO by request id).  Returns True when a chunk
        was dispatched (watchdog progress)."""
        pick = None
        for slot, rec in enumerate(self._slot_prefill):
            if rec is None:
                continue
            if pick is None or self._slot_req[slot].id < self._slot_req[pick].id:
                pick = slot
        if pick is None:
            return False
        slot, rec = pick, self._slot_prefill[pick]
        req = self._slot_req[slot]
        c = self._prefill_chunk
        done = rec["done"]
        suffix = req.tokens[done:done + c]
        t_c0 = self.clock()
        try:
            padded = np.full((1, c), self.pad_id, np.int32)
            padded[0, : suffix.size] = suffix
            # ONE program per chunk SIZE, not per prompt length: every
            # chunk of every prompt is this same (1, C) extend — the
            # census stays pinned and long prompts need no bucket
            with self._compile.site(self._site(f"extend[b{c}]")):
                self.cache, ext_logits = self._extend(
                    self.params, self.cache, jnp.asarray(slot, jnp.int32),
                    rec["bt_dev"], jnp.asarray(padded),
                    jnp.asarray(done, jnp.int32),
                    jnp.asarray(int(suffix.size), jnp.int32))
            rec["done"] = done + int(suffix.size)
            rec["last"] = ext_logits
            # the extend installed the slot's real block table — a reset
            # pending from the previous tenant must not zero it back
            reset_mask[slot] = False
            t_c1 = self.clock()
            self.stats.chunk(t_c1 - t_c0, start=done)
            if self._recurrent:
                self._sample_row_leaves()
            if self._experts:
                self.stats.expert_tokens(
                    self.model.expert_pairs(int(suffix.size)))
            if self._ssm_layers:
                self.stats.ssm_scan(int(suffix.size) * self._ssm_layers)
            if self._tracer is not None and req.trace is not None:
                # per-chunk child span under the request's admit phase
                self._tracer.complete(
                    "prefill_chunk", t_c0, t_c1, cat="serving",
                    parent=req.trace.get("phase") or req.trace["id"],
                    tid=req.trace["tid"], offset=done,
                    tokens=int(suffix.size))
            return True
        except Exception as e:
            # the chunk's failure is THIS request's failure (isolated) —
            # the slot frees and its pages queue for release
            self._slot_req[slot] = None
            self._slot_prefill[slot] = None
            self._release_slot_alloc(slot)
            self._active_dev = None
            self._fail(req, e, self.clock())
            reset_mask[slot] = True
            return False

    def _chunk_finish(self, slot: int, rec: dict, req: Request,
                      reset_mask) -> None:
        """The last chunk landed: pick the first token from its final-
        position logits (the shared ``first_pick`` program — same as
        every other landing path), donate the freshly-prefilled whole
        prompt pages into the radix trie, and run the standard admission
        tail (TTFT/SLO/telemetry, streaming callback, planes, decode
        phase).  Failure here is the request's own, exactly like the
        whole-prompt admission tail."""
        now = self.clock()
        if self.role == "prefill":
            # disaggregated (ISSUE 16): donate the freshly-chunked prompt
            # blocks into the source trie, then package instead of
            # picking — chunked prefill composes with handoff exactly as
            # with local decode
            try:
                if self._radix is not None:
                    n_tok = int(req.tokens.size)
                    bt_row, m_blocks = rec["bt"], rec["m_blocks"]
                    donate = {j: int(bt_row[j])
                              for j in range(m_blocks,
                                             n_tok // self._page_size)}
                    if donate:
                        priv, nodes = self._slot_alloc[slot]
                        held, _kept = self._radix.insert(
                            req.tokens, m_blocks, donate, rec["path"])
                        for node in held:
                            priv.remove(node.page)
                            nodes.append(node)
                self._handoff_package(req, slot, rec["last"], rec["bt"])
            except Exception as e:
                self._slot_req[slot] = None
                self._slot_prefill[slot] = None
                self._release_slot_alloc(slot)
                self._active_dev = None
                self._fail(req, e, self.clock())
                reset_mask[slot] = True
                return
            self._slot_req[slot] = None
            self._slot_prefill[slot] = None
            self._active_dev = None
            reset_mask[slot] = True
            return
        try:
            first, first_logp = self._first_pick(req, rec["last"])
            if self._radix is not None:
                n_tok = int(req.tokens.size)
                bt_row, m_blocks = rec["bt"], rec["m_blocks"]
                donate = {j: int(bt_row[j])
                          for j in range(m_blocks, n_tok // self._page_size)}
                if donate:
                    priv, nodes = self._slot_alloc[slot]
                    held, _kept = self._radix.insert(
                        req.tokens, m_blocks, donate, rec["path"])
                    for node in held:
                        priv.remove(node.page)
                        nodes.append(node)
            req.generated.append(first)
            req.logprobs.append(first_logp)
            req.first_token_t = self.clock()  # TTFT: first token ON THE HOST
            self._last_progress_ever = req.first_token_t
            if req.ttft_slo_s is not None:
                req.slo_ttft_ok = (
                    req.first_token_t - req.submit_t <= req.ttft_slo_s)
            if self._telemetry is not None:
                self._telemetry.observe(
                    "ttft_s", req.first_token_t - req.submit_t,
                    exemplar=(req.trace_ctx.trace_id
                              if req.trace_ctx is not None else None))
                self._telemetry.inc("tokens_generated")
            req.status = "running"
            self._tr_instant(req, "first_token", slot=slot,
                             cache_hit=False)
            self._notify(req, first)
        except Exception as e:
            self._slot_req[slot] = None
            self._slot_prefill[slot] = None
            self._release_slot_alloc(slot)
            self._active_dev = None
            self._fail(req, e, self.clock())
            reset_mask[slot] = True
            return
        self._slot_prefill[slot] = None
        self._slot_tok[slot] = first
        temp, topp, topk, minp, key = self._req_sampling(req)
        self._slot_temp[slot] = temp
        self._slot_topp[slot] = topp
        self._slot_topk[slot] = topk
        self._slot_minp[slot] = minp
        self._slot_key[slot] = key
        self._tok_dev = None  # host mirrors changed; re-upload
        self._active_dev = None
        self._planes_dev = None
        self._pos_dev = None
        self._tr_phase(req, "decode", slot=slot)
        if self._done_reason(req) is not None:
            self._retire(slot, self._done_reason(req), self.clock())
            reset_mask[slot] = True

    def _chunk_land(self, reset_mask) -> None:
        """Land any slot whose LAST chunk has been dispatched.  Runs
        AFTER the window readback (not at the dispatch seam) so the
        landing's host-mirror writes — ``_slot_tok[slot]``, the sampling
        planes, the mirror invalidations — are not clobbered by the
        readback's wholesale ``blk[:, -1]`` copy."""
        for slot, rec in enumerate(self._slot_prefill):
            if rec is None or rec["last"] is None:
                continue
            req = self._slot_req[slot]
            if rec["done"] >= int(req.tokens.size):
                self._chunk_finish(slot, rec, req, reset_mask)

    def _admit_free_slots(self, reset_mask) -> bool:
        """Fill free slots: overlap-prefilled pendings first (they were
        popped earlier, so FIFO order is preserved), then fresh scheduler
        pops.  A failed admission (poisoned request) frees the slot for
        the NEXT request in the same iteration — one casualty must not
        idle a slot for a whole loop turn.  Returns True when anything
        landed (watchdog progress)."""
        admitted = False
        for slot in range(self.slots):
            while self._slot_req[slot] is None:
                if self._pending:
                    req, prefilled = self._pending.popleft()
                    self._stalled_ids.discard(req.id)
                    now = self.clock()
                    if now > req.overdue_at:
                        # the overlap gamble lost: prefilled, then the
                        # deadline lapsed before a slot freed — cancel
                        # without landing (the prefill is sunk cost)
                        req.status = "cancelled"
                        req.finish_t = now
                        self._tr_close(req, status="cancelled")
                        self.completed.append(req)
                        self.stats.add(req)
                        continue
                    needs_reset = self._admit(req, slot, now,
                                              prefilled=prefilled)
                else:
                    req = self.scheduler.pop(self.clock())
                    if req is None:
                        return admitted
                    needs_reset = self._admit(req, slot, self.clock())
                if isinstance(needs_reset, tuple):
                    # paged pool momentarily dry ("stall", prefilled): park
                    # the request at the FRONT (FIFO preserved — it was
                    # popped first) and stop admitting; this step's retires
                    # flush pages and the next iteration retries
                    self._pending.appendleft((req, needs_reset[1]))
                    self._stalled_ids.add(req.id)
                    return admitted
                if self._slot_req[slot] is not None:
                    admitted = True
                    if self._slot_prefill[slot] is None:
                        reset_mask[slot] = False  # insert overwrote the row
                    # else PREFILLING: keep any pending reset — the block
                    # table must stay TRASH until a chunk installs it
                elif needs_reset:
                    # the row was claimed but belongs to no live request
                    # (post-insert failure, or retired at admission); zero
                    # it unless a later admit in this loop overwrites it
                    reset_mask[slot] = True
        return admitted

    def _overlap_prefill(self) -> None:
        """Dispatch the NEXT queued request's bucketed prefill while a
        decode window is still in flight — the prefill's compute hides
        behind the window instead of stalling every resident slot at the
        next admission.  At most one dispatch per window (matching the
        at-most-slots admission rate) and at most ``slots`` parked
        pendings; a failure here is the request's own (isolated), exactly
        as if it had failed at inline admission."""
        if len(self._pending) >= self.slots:
            return
        req = self.scheduler.pop(self.clock())
        if req is None:
            return
        # the "admit" phase opens HERE — for an overlapped request it spans
        # prefill + the parked wait for a slot, mirroring what the request
        # actually experiences between queue exit and its first token
        self._tr_phase(req, "admit", overlapped=True)
        try:
            self._pending.append((req, self._prefill_request(req)))
        except Exception as e:
            self._fail(req, e, self.clock())

    # ------------------------------------------------------------------
    # disaggregated prefill/decode handoff (ISSUE 16; serving/kv_handoff)

    def _handoff_package(self, req: Request, slot: int, logits_dev,
                         bt_row) -> None:
        """Prefill role: gather the landed prompt's pages host-side and
        park the request in the outbox (kv_handoff.package) — the slot's
        page hold transfers to the packet, nothing frees until the router
        confirms delivery."""
        packet = kv_handoff.package(self, req, slot, logits_dev, bt_row)
        self._outbox.append(packet)
        self.handoffs_out += 1

    def admit_prefilled(self, packet) -> bool:
        """Decode side: land a handed-off prefill (kv_handoff.deliver).
        True = packet consumed (decoding, or terminally failed on its own
        admission tail); False = re-park and retry later (no free slot,
        or the all-or-nothing destination allocation found the pool dry —
        zero writes were issued).  Refused on prefill-role and dense
        engines, and after close."""
        if self._closed:
            raise RuntimeError("engine is closed")
        if self.role == "prefill":
            raise RuntimeError(
                "prefill-role engine cannot accept a handoff — deliver to "
                "a decode/both replica")
        if self._pool is None:
            raise RuntimeError(
                "handoff needs the paged KV layout (kv_page_size > 0)")
        return kv_handoff.deliver(self, packet)

    def _reset_slot_now(self, slot: int) -> None:
        """Immediate one-slot block-table reset + deferred-free flush,
        for landing paths that run OUTSIDE step() (admit_prefilled): the
        reset dispatch precedes any later tenant of the reclaimed pages
        on the single device stream, same as step()'s batched reset."""
        mask = np.zeros((self.slots,), bool)
        mask[slot] = True
        with self._compile.site(self._site("slot_reset")):
            self.cache = self._reset(self.cache, self._dev(mask))
        self._flush_freed_pages()

    def step(self) -> int:
        """One host-loop iteration: cancel → admit → decode window →
        retire.  Returns the number of REAL tokens produced this
        iteration (window tokens past a row's EOS/budget are discarded,
        never counted).

        Each phase is a :func:`host_span` on the profiler's clock
        (``engine.step`` around ``engine.admit`` / ``dispatch`` /
        ``overlap`` / ``readback`` / ``emit`` / ``reset``; table in
        docs/OBSERVABILITY.md): free with no profiler session, and under
        one they say what the host did in each idle gap of the device."""
        if self._closed:
            raise RuntimeError("engine is closed")
        with host_span("engine.step", occupied=self.occupied):
            return self._step()

    def _step(self) -> int:
        t0 = self.clock()
        reset_mask = np.zeros((self.slots,), bool)

        # 1) deadline sweep over RUNNING rows (queued rows are swept by the
        #    scheduler at pop time; overlap-prefilled pendings at landing)
        for slot, req in enumerate(self._slot_req):
            if req is not None and t0 > req.overdue_at:
                self._retire(slot, "cancelled", t0)
                reset_mask[slot] = True

        # 2) admit into free slots — freed capacity refills immediately,
        #    which is the whole point of continuous batching
        with host_span("engine.admit"):
            admitted = self._admit_free_slots(reset_mask)

        # 3) ONE windowed decode dispatch across ALL slots (fixed shape;
        #    idle rows decode garbage into their own rows).  The active
        #    mask is FROZEN for the window: rows retiring mid-window keep
        #    decoding up to decode_ahead-1 garbage steps the host masks
        #    off below.  A decode-dispatch fault belongs to ALL slots:
        #    with a watchdog it is absorbed as a no-progress iteration
        #    until stall_timeout_s, then in-flight requests fail and
        #    EngineStalled raises; without one it fails in-flight and
        #    re-raises immediately.
        produced = 0
        decoded = False
        chunked = False
        occupied_at_dispatch = self.occupied
        # PREFILLING slots are occupied but not decoding: a window with
        # zero decoding rows would be pure waste (and a spurious
        # serving-step chaos event), so the dispatch gates on decoding
        decoding_at_dispatch = (self._decoding if self._prefill_chunk
                                else occupied_at_dispatch)
        if decoding_at_dispatch > 0:
            spec = self._verify is not None
            # speculative mode replaces the decode-ahead scan with ONE
            # (slots, draft_len+1)-position verify forward per window —
            # host drafting must run between windows, which a fused k-step
            # scan could never pause for — so the window length k is the
            # verify chunk size, not decode_ahead
            k = self.draft_len + 1 if spec else self.decode_ahead
            # the engine-track (tid 0) view of this window; request-track
            # spans tell each request's story, this tells the loop's.
            # Emitted as already-closed `complete` spans from the stats
            # timestamps the loop takes anyway — the windowed hot path
            # pays 3 ring pushes per window, no open-span churn and no
            # tracer-only clock reads.
            t_w0 = self.clock() if self._tracer is not None else 0.0
            t_disp = None
            try:
                if self._chaos is not None:
                    from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
                        ChaosFault,
                    )

                    # one chaos event per WINDOW dispatch (not per fused
                    # step): the event index is the dispatch count, which
                    # keeps seeded plans stable across decode_ahead
                    self._chaos.raise_if_fired("serving-step", ChaosFault)
                with host_span("engine.dispatch", k=k):
                    if spec:
                        # ---- host drafting: build the (slots, k) chunk ----
                        # column 0 = each slot's pending last token (the same
                        # contract the decode window's tok carry uses), then
                        # up to draft_len prompt-lookup proposals per slot
                        t_d0 = self.clock()
                        chunk = np.full((self.slots, k), self.pad_id, np.int32)
                        chunk[:, 0] = self._slot_tok
                        dls = np.zeros((self.slots,), np.int32)
                        for slot, req in enumerate(self._slot_req):
                            if req is None or self._slot_prefill[slot] is not None:
                                continue
                            d = self._drafter.draft(np.concatenate(
                                [req.tokens,
                                 np.asarray(req.generated, np.int32)]))
                            if d.size:
                                chunk[slot, 1:1 + d.size] = d
                                dls[slot] = d.size
                        with self._compile.site(self._site("slot_draft")):
                            chunk_dev = self._dev(chunk)
                            dls_dev = self._dev(dls)
                            # acceptance makes the PRNG position advance
                            # data-dependent: spec windows re-upload the plane
                            # fresh from the host generated counts each window
                            pos_dev = self._dev(np.array(
                                [0 if r is None else len(r.generated)
                                 for r in self._slot_req], np.int32))
                        t_d1 = self.clock()
                    else:
                        if self._tok_dev is None:
                            self._tok_dev = self._dev(self._slot_tok)
                        if self._pos_dev is None:
                            # PRNG positions = tokens generated so far; the
                            # window returns the advanced plane (carried like
                            # _tok_dev, rebuilt here after any admission)
                            self._pos_dev = self._dev(np.array(
                                [0 if r is None else len(r.generated)
                                 for r in self._slot_req], np.int32))
                    if self._active_dev is None or self._planes_dev is None:
                        # PREFILLING slots stay INACTIVE: their pages hold a
                        # partial prompt — garbage decode writes above the
                        # chunk cursor are overwritten by the next chunk
                        active = np.array(
                            [r is not None and p is None
                             for r, p in zip(self._slot_req,
                                             self._slot_prefill)])
                        self._active_dev = self._dev(active)
                        if self._planes_dev is None:
                            self._planes_dev = (self._dev(self._slot_temp),
                                                self._dev(self._slot_topp),
                                                self._dev(self._slot_topk),
                                                self._dev(self._slot_minp),
                                                self._dev(self._slot_key))
                        # which branches of the pick these windows take: the
                        # device's own predicate (core/generate.py pick_work)
                        # on the mirrors of what was just uploaded
                        self._pick_work = tuple(map(bool, pick_work(
                            active, self._slot_temp, self._slot_topp,
                            self._slot_topk, self.model.num_classes)))
                    (temps_dev, topps_dev, topks_dev, minps_dev,
                     keys_dev) = self._planes_dev
                    t_disp = self.clock()
                    if spec:
                        with self._compile.site(self._site(f"verify_window[k{k}]")):
                            self.cache, blk_dev, logp_dev, acc_dev, _ = \
                                self._verify(
                                    self.params, self.cache, chunk_dev, dls_dev,
                                    self._active_dev, temps_dev, topps_dev,
                                    topks_dev, minps_dev, keys_dev, pos_dev)
                    else:
                        if self._recurrent:
                            self._count_recurrent_window()
                        with self._compile.site(self._site(f"decode_window[k{k}]")):
                            self.cache, blk_dev, logp_dev, last_dev, pos_out = \
                                self._window(
                                    self.params, self.cache, self._tok_dev,
                                    self._active_dev, temps_dev, topps_dev,
                                    topks_dev, minps_dev, keys_dev,
                                    self._pos_dev)
                    dispatch_s = self.clock() - t_disp
            except Exception as e:
                now = self.clock()
                if self._tracer is not None:
                    # a decode-dispatch fault belongs to ALL slots — the
                    # engine-track instant records it once; requests it
                    # kills get their own chaos_fault/close via _fail
                    self._tracer.instant(
                        "decode_fault", cat="serving", tid=self._trace_tid,
                        error=f"{type(e).__name__}: {e}")
                    wid = self._tracer.complete(
                        "window", t_w0, now, cat="serving", k=k,
                        tid=self._trace_tid, occupied=occupied_at_dispatch,
                        error=type(e).__name__)
                    if t_disp is not None:
                        self._tracer.complete(
                            "dispatch", t_disp, now, cat="serving",
                            tid=self._trace_tid, parent=wid,
                            error=type(e).__name__)
                anchor = self._last_progress_t if self._last_progress_t is not None else t0
                if self._last_progress_t is None:
                    self._last_progress_t = t0
                if self.stall_timeout_s is None:
                    self._fail_in_flight(e, now)
                    raise
                if now - anchor > self.stall_timeout_s:
                    self._fail_in_flight(e, now)
                    raise EngineStalled(
                        f"no token progress across {self.slots} slots within "
                        f"{self.stall_timeout_s}s (last decode error: "
                        f"{type(e).__name__}: {e})") from e
                # transient: no tokens this iteration, watchdog keeps counting
            else:
                decoded = True
                # the window is in flight (async dispatch): spend the wait
                # prefilling instead of blocking — one chunk of the oldest
                # PREFILLING slot in chunked mode, else the next queued
                # request's bucketed prefill
                with host_span("engine.overlap"):
                    if self._prefill_chunk:
                        chunked = self._chunk_tick(reset_mask)
                    else:
                        self._overlap_prefill()
                # ONE blocking host sync per window: the (slots, k) block
                # serves the host inspection below, and `last` (the final
                # carry token) feeds the next window without a host slice
                with host_span("engine.readback"):
                    t_rb = self.clock()
                    blk = np.asarray(blk_dev)
                    logps = np.asarray(logp_dev)
                    acc = np.asarray(acc_dev) if spec else None
                    readback_s = self.clock() - t_rb
                if spec:
                    # each slot's pending token is acceptance-dependent —
                    # set per slot below; the device token mirror is never
                    # read in spec mode (the chunk re-uploads fresh)
                    self._tok_dev = None
                else:
                    self._tok_dev = last_dev
                    self._pos_dev = pos_out  # advanced in-graph, carried
                    self._slot_tok = blk[:, -1].copy()
                now = self.clock()
                t_acc0 = t_rb + readback_s
                waste = 0
                with host_span("engine.emit"):
                    for slot, req in enumerate(self._slot_req):
                        if req is None or self._slot_prefill[slot] is not None:
                            continue  # PREFILLING rows were inactive: no tokens
                        n_emit = k
                        if spec:
                            # accepted drafts + the model's one free correction
                            # token: emitted tokens are exactly blk[:, :acc+1]
                            n_emit = int(acc[slot]) + 1
                            self._slot_tok[slot] = blk[slot, n_emit - 1]
                            self.stats.spec(int(dls[slot]), int(acc[slot]))
                            if self._tracer is not None and req.trace is not None:
                                # draft/verify/accept land on the REQUEST's
                                # track BEFORE the token loop, so a mid-
                                # acceptance retirement (which closes the
                                # request's trace tree) cannot lose them
                                par = req.trace.get("phase") or req.trace["id"]
                                rtid = req.trace["tid"]
                                self._tracer.complete(
                                    "draft", t_d0, t_d1, cat="speculative",
                                    parent=par, tid=rtid, drafted=int(dls[slot]))
                                self._tracer.complete(
                                    "verify", t_disp, t_acc0, cat="speculative",
                                    parent=par, tid=rtid)
                                self._tracer.complete(
                                    "accept", t_acc0, now, cat="speculative",
                                    parent=par, tid=rtid,
                                    accepted=int(acc[slot]),
                                    drafted=int(dls[slot]))
                        appended = 0
                        for j in range(n_emit):
                            tok = int(blk[slot, j])
                            req.generated.append(tok)
                            req.logprobs.append(float(logps[slot, j]))
                            produced += 1
                            appended += 1
                            try:
                                self._notify(req, tok)
                            except Exception as e:
                                # the callback's failure is THIS request's
                                # failure; its remaining window tokens die with it
                                self._slot_req[slot] = None
                                self._release_slot_alloc(slot)
                                self._active_dev = None
                                self._fail(req, e, now)
                                reset_mask[slot] = True
                                break
                            reason = self._done_reason(req)
                            if reason is not None:
                                # EOS/budget mid-window: keep tokens up to and
                                # including the stop, discard the ≤k-1 overrun
                                self._retire(slot, reason, now,
                                             waste=k - appended)
                                reset_mask[slot] = True
                                break
                        # this slot dispatched k device steps (scan steps in
                        # plain mode, verify lanes in spec mode) and delivered
                        # `appended` tokens — the remainder (post-stop overrun
                        # / rejected lanes) is the window's waste
                        waste += k - appended
                self.stats.window(dispatch_s, readback_s,
                                  steps=decoding_at_dispatch * k, waste=waste,
                                  paged_kernel=self._paged_kernel,
                                  sampled=self._pick_work[0],
                                  sorted_=self._pick_work[1])
                if self._tracer is not None:
                    wid = self._tracer.complete(
                        "window", t_w0, self.clock(), cat="serving", k=k,
                        tid=self._trace_tid, occupied=occupied_at_dispatch,
                        produced=produced, waste=waste)
                    self._tracer.complete("dispatch", t_disp,
                                          t_disp + dispatch_s, cat="serving",
                                          tid=self._trace_tid, parent=wid)
                    self._tracer.complete("readback", t_rb,
                                          t_rb + readback_s, cat="serving",
                                          tid=self._trace_tid, parent=wid)

        if self._prefill_chunk:
            if not decoded:
                # nothing decoding (every occupied slot PREFILLING, or the
                # window faulted): chunks still pump — one per iteration
                chunked = self._chunk_tick(reset_mask)
            # land AFTER the readback so the wholesale _slot_tok copy
            # above cannot clobber a landed request's first token
            self._chunk_land(reset_mask)

        # 4) zero retired rows so idle cursors restart from 0 (bounded) and
        #    the next admission starts from a clean row
        with host_span("engine.reset"):
            if reset_mask.any():
                if self._paged_kernel:
                    # every row nobody holds restarts with them, in the same
                    # dispatch: an idle row's cursor counts one garbage
                    # token a window and the paged decode kernel reads as
                    # many trash-page positions as the cursor says, so a
                    # window's device time climbed with the windows since a
                    # slot was last used (0.20 -> 0.48 ms a layer for 62
                    # idle rows at 4096, v5e)
                    for slot, req in enumerate(self._slot_req):
                        if req is None:
                            reset_mask[slot] = True
                with self._compile.site(self._site("slot_reset")):
                    self.cache = self._reset(self.cache, self._dev(reset_mask))
            # deferred page frees apply only now, AFTER the reset dispatch is
            # enqueued: single-stream device execution guarantees every program
            # still reading a retired slot's block table runs before any later
            # tenant of the reallocated pages writes them
            self._flush_freed_pages()

        if produced > 0 or admitted or chunked or self.occupied == 0:
            self._last_progress_t = self.clock()
            self._last_progress_ever = self._last_progress_t
        if self._pool is not None:
            self.stats.pool_sample(self._pool.allocated, self._pool.capacity,
                                   self._page_size, self._page_bytes)
        self.stats.tick(self.occupied, max(self.clock() - t0, 0.0),
                        decoded=decoded)
        # counters only at their change points (admission shrinks the
        # queue, retirement frees slots) — the tracer dedups repeats
        # anyway, but the calls themselves are hot-loop cost
        if self._tracer is not None and (admitted or reset_mask.any()):
            self._tracer.counter("queue_depth", len(self.scheduler),
                                 tid=self._trace_tid)
            self._tracer.counter("occupied_slots", self.occupied,
                                 tid=self._trace_tid)
        if self._telemetry is not None:
            if produced:
                self._telemetry.inc("tokens_generated", int(produced))
            self._telemetry.maybe_sample()  # clock + compare between samples
        return produced

    def _fail_in_flight(self, exc: BaseException, now: float) -> None:
        """Fail every running request and reset their rows — the clean-exit
        half of the watchdog contract (the engine stays consistent for a
        caller that catches EngineStalled)."""
        mask = np.zeros((self.slots,), bool)
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            self._slot_req[slot] = None
            self._slot_prefill[slot] = None
            self._release_slot_alloc(slot)
            req.engine_fault = True  # collateral, not the request's own fault
            self._fail(req, exc, now)
            mask[slot] = True
        if mask.any():
            self.cache = self._reset(self.cache, self._dev(mask))
        self._flush_freed_pages()
        self._active_dev = None
        self._planes_dev = None
        self._pos_dev = None
        self._last_progress_t = None

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Drive :meth:`step` until every submitted request has retired
        (or ``max_steps`` host iterations elapse), then return the
        completed requests in retirement order.  Emits the stats summary
        through ``writer`` (when one was given) on drain."""
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        # overdue-before-admission cancellations belong to this run's book
        for req in self.scheduler.cancelled:
            self.completed.append(req)
            self.stats.add(req)
        self.scheduler.cancelled.clear()
        if not self.has_work:
            if self._prefix is not None:
                self.stats.prefix_oversized(self._prefix.oversized)
            self.stats.set_compile(CompileTracker.delta(
                self._compile.snapshot(), self._compile0))
            self._stamp_memory()
            self.sync_expert_load()
            if self.writer is not None:
                self.stats.emit(self.writer)
        return self.completed

    # ------------------------------------------------------------------
    # graceful shutdown

    def drain(self, max_steps: int | None = None) -> list[Request]:
        """Graceful shutdown, phase 1: serve every request already accepted
        (queued + in-flight) to retirement, admitting NOTHING new —
        :meth:`submit` raises from the moment drain starts.  Returns the
        completed list; call :meth:`close` afterwards to release the
        engine."""
        if self._closed:
            raise RuntimeError("engine is closed")
        self._draining = True
        return self.run(max_steps=max_steps)

    def close(self) -> None:
        """Graceful shutdown, phase 2 (or an immediate one): cancel every
        queued and in-flight request (terminal ``cancelled``, partial
        output kept), emit the stats record, and refuse all further
        submit/step/run/drain calls.  A parked request whose landing
        STALLED on a dry page pool (overcommit) is instead FAILED
        terminally — it was accepted and then starved, not merely queued.
        Every request terminated here carries ``engine_fault=True`` (the
        engine quit on it; a router re-dispatches exactly these).
        Idempotent."""
        if self._closed:
            return
        self._draining = True
        now = self.clock()
        mask = np.zeros((self.slots,), bool)
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            req.engine_fault = True
            self._retire(slot, "cancelled", now)
            mask[slot] = True
        if mask.any():
            self.cache = self._reset(self.cache, self._dev(mask))
        self._flush_freed_pages()
        while self._outbox:
            # packaged-but-undelivered handoffs: accepted work the engine
            # quit on — cancelled with engine_fault, so the router's
            # failover harvest re-dispatches exactly these (the replay's
            # re-prefill is a radix hit wherever the trie survives)
            packet = self._outbox.popleft()
            packet.release()
            req = packet.req
            req.engine_fault = True
            req.status = "cancelled"
            req.finish_t = now
            self._tr_close(req, status="cancelled")
            self.completed.append(req)
            self.stats.add(req)
        for req, _prefilled in self._pending:  # overlap-prefilled, unlanded
            req.engine_fault = True
            if req.id in self._stalled_ids:
                self._fail(req, RuntimeError(
                    "engine closed while the request was overcommit-stalled "
                    "(accepted, prefilled, starved of KV pages)"), now)
            else:
                req.status = "cancelled"
                req.finish_t = now
                self._tr_close(req, status="cancelled")
                self.completed.append(req)
                self.stats.add(req)
        self._pending.clear()
        self._stalled_ids.clear()
        while (req := self.scheduler.pop(now)) is not None:
            req.engine_fault = True
            req.status = "cancelled"
            req.finish_t = now
            self._tr_close(req, status="cancelled")
            self.completed.append(req)
            self.stats.add(req)
        for req in self.scheduler.cancelled:  # overdue-at-pop sweepings
            self.completed.append(req)
            self.stats.add(req)
        self.scheduler.cancelled.clear()
        if self._prefix is not None:
            self.stats.prefix_oversized(self._prefix.oversized)
        if self._pool is not None:  # final occupancy (post-cancel flush)
            self.stats.pool_sample(self._pool.allocated, self._pool.capacity,
                                   self._page_size, self._page_bytes)
        self.stats.set_compile(CompileTracker.delta(
            self._compile.snapshot(), self._compile0))
        self._stamp_memory()
        self.sync_expert_load()
        if self.writer is not None:
            self.stats.emit(self.writer)
        self._closed = True

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ------------------------------------------------------------------
    # live weight replacement

    def swap_params(self, params) -> None:
        """Replace the decode weights of an IDLE engine in place — the
        replica half of the router's hot-swap (drain → swap → re-admit).

        The engine must be fully quiesced (no occupied slot, no parked
        pending, no queued request): every cached KV entry was computed
        under the OLD weights, so a swap with work in flight would splice
        old-weight keys/values into new-weight attention.  For the same
        reason the prefix cache and the radix trie are dropped wholesale —
        their entries are stale the instant the weights change — with the
        trie's pages returned to the pool.  The compiled program family is
        shape-keyed, not weight-keyed, so NO recompilation follows: the
        swapped engine serves its first new-weight request at full speed.
        """
        if self._closed:
            raise RuntimeError("engine is closed")
        if self.has_work or self._outbox:
            # a parked handoff packet HOLDS pool pages and radix nodes —
            # the wholesale trie eviction below assumes no outstanding
            # references, so an undelivered outbox counts as busy too
            raise RuntimeError(
                f"swap_params on a busy engine (occupied={self.occupied}, "
                f"pending={len(self._pending)}, queued={len(self.scheduler)}, "
                f"outbox={len(self._outbox)})"
                " — drain it first (stop submitting, pump step() until "
                "has_work is False)")
        if self.quant == "int8":
            # the hot-swap contract hands FULL-PRECISION host trees (the
            # router gives every replica the same checkpoint): re-quantize
            # to the engine's int8+scale layout before placement.  A tree
            # that already carries int8 kernels passes through unchanged
            # (quantize_params_int8 is idempotent).
            params = quantize_params_int8(params)
        # accepts a full host/single-chip tree and places it wholesale in
        # THIS engine's layout — its mesh, or its one chip (the router's
        # hot-swap hands every replica the same unsharded checkpoint
        # tree); an already-correctly-placed tree is a no-op
        self.params = jax.device_put(params, self._param_shardings)
        if self._prefix is not None:
            self._prefix.clear()
        if self._radix is not None:
            # every node is unreferenced on an idle engine; evict the lot
            self._radix.evict(self._radix.n_blocks,
                              lambda p: self._pool.free([p]))

    # ------------------------------------------------------------------
    # launch-path compile prewarm (ROADMAP item 5a)

    def prewarm(self) -> dict:
        """Compile the engine's ENTIRE program family before the first
        request — the launch-path half of the cold-start fix (ROADMAP item
        5a; the persistent compile cache — utils/compile_cache.py — is the
        cross-process half: these compiles land there).

        Runs each resident program once with zero/dummy inputs on the IDLE
        engine: every bucket's prefill, the shared first-token pick, the
        window program this mode actually dispatches (decode window, or
        the verify window in speculative mode), the slot insert/reset,
        and — paged — every bucket's suffix-extend.  Execution (not
        ``lower().compile()``) is deliberate: it populates the real jit
        call caches, so the first request pays ZERO compile anywhere, and
        the compile events fire under the same ``CompileTracker`` site
        labels they would at first use — the census budget sees the
        identical program family, just earlier.  Dummy work is confined
        to idle-slot garbage the engine's contract already tolerates
        (all-inactive masks, the trash page, rows an insert overwrites at
        admission), and sampling keys are pure per-request data (no
        engine-held stream to perturb), so prewarmed output is
        token-identical to cold output.

        Returns ``{"programs", "compile_s", "wall_s", "by_site"}`` — the
        compile delta this call caused (0 programs on a warm persistent
        cache is the success case).
        """
        if self._closed:
            raise RuntimeError("engine is closed")
        if self.has_work:
            raise RuntimeError(
                f"prewarm on a busy engine (occupied={self.occupied}, "
                f"pending={len(self._pending)}, "
                f"queued={len(self.scheduler)}) — prewarm belongs in the "
                "launch path, before the first submit")
        t0 = self.clock()
        before = self._compile.snapshot()
        slot0 = jnp.asarray(0, jnp.int32)
        if self.role == "decode":
            # decode replicas own NO prefill program: pages arrive via
            # admit_prefilled (serving/kv_handoff.py), so the family here
            # is first_pick + the decode window + reset + the per-page
            # handoff writer — and the per-role census
            # (tests/test_disagg.py) pins that no prefill[b*]/extend[b*] site ever appears
            vocab = getattr(self.model, "num_classes")
            last_logits = self._dev(np.zeros((1, vocab), np.float32))
            with self._compile.site(self._site("handoff_install")):
                # zero payload through the SAME _dev commitment the real
                # admit_prefilled upload uses, so tp engines compile one
                # page-writer here and reuse it for every handoff
                payload = jax.tree.map(
                    lambda leaf: self._dev(
                        np.zeros(leaf.shape[1:], leaf.dtype)),
                    pool_page_leaves(self.cache))
                self.cache = self._page_write(self.cache, payload, slot0)
                bt_row = self._dev(np.zeros(
                    (self.max_len // self._page_size,), np.int32))
                self.cache = self._bt_install(
                    self.cache, bt_row, slot0, jnp.asarray(0, jnp.int32))
        elif self._prefill_chunk:
            # chunked mode never dispatches bucketed prefills or the
            # dense slot insert: the resident prefill family is the ONE
            # extend[b{C}] program every chunk of every prompt runs
            # through, warmed here over the trash-page block table
            # (garbage K/V the admission protocol already tolerates)
            c = self._prefill_chunk
            bt_row = self._dev(np.zeros((self.max_len // self._page_size,),
                                        np.int32))
            with self._compile.site(self._site(f"extend[b{c}]")):
                self.cache, last_logits = self._extend(
                    self.params, self.cache, slot0, bt_row,
                    jnp.zeros((1, c), jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(1, jnp.int32))
            if self._experts:  # the warm chunk's one real token was routed
                self.stats.expert_tokens(self.model.expert_pairs(1))
            if self._ssm_layers:  # and scanned
                self.stats.ssm_scan(self._ssm_layers)
        else:
            last_logits = None
            for b in self.buckets:
                with self._compile.site(self._site(f"prefill[b{b}]")):
                    # lens through the same list->asarray route
                    # _dense_prefill uses, so its scalar-conversion
                    # program is warm too, not just the prefill itself
                    _, last_logits = self._prefill_row(
                        self.params, jnp.zeros((1, b), jnp.int32),
                        jnp.asarray([1], jnp.int32))
        if self.role == "prefill":
            # the source half of the handoff family: the ONE fixed-shape
            # page gather every transferred page reads through (read-only
            # — jitted without donation), warmed so the first packet pays
            # zero compile
            with self._compile.site(self._site("handoff_gather")):
                jax.block_until_ready(self._page_gather(
                    self.cache, jnp.asarray(0, jnp.int32)))
        # the shared first-token pick over the (1, V) prefill logits —
        # same program whatever landing path (miss/hit/extend/handoff)
        # runs it.  A prefill-role engine never picks a token (the pick
        # runs on the decode side from the handed-off logits row), so it
        # skips this — its census carries zero pick/decode programs.
        if self.role != "prefill":
            with self._compile.site(self._site("first_pick")):
                tok, logp = first_pick(
                    last_logits,
                    self._dev(np.zeros((1,), np.float32)),
                    self._dev(np.zeros((1,), np.float32)),
                    self._dev(np.zeros((1,), np.int32)),
                    self._dev(np.zeros((1,), np.float32)),
                    self._dev(np.zeros((1, 2), np.uint32)),
                    self._dev(np.zeros((1,), np.int32)))
                # the landing path reads the pick eagerly (_first_pick
                # returns python scalars); under a mesh those committed
                # outputs key their own tiny gather programs, so read
                # them here or the first real admission compiles them
                int(tok[0]), float(logp[0])
        if not self._prefill_chunk and self.role != "decode":
            # a zeroed B=1 prefill row in the dense decode layout — the
            # same eval_shape probe init_cache uses, so dtypes (incl.
            # int8+scales) match what a real prefill hands to insert
            row_shapes = jax.eval_shape(
                lambda p: self.model.apply(
                    {"params": p}, jnp.zeros((1, 1), jnp.int32),
                    decode=True, max_len=self.max_len, ragged=True,
                    mutable=["cache"])[1]["cache"],
                self.params)
            row_cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), row_shapes)
            # match the layout (and commitment) a REAL prefill's pinned
            # output arrives in, so prewarm compiles the same insert
            # program serving reuses
            row_cache = jax.device_put(
                row_cache,
                self._rep if self._mesh is None else mesh_shardings(
                    self._mesh, make_param_specs(row_shapes, self._kv_rule)))
            if self._pool is not None:
                bt_row = self._dev(
                    np.zeros((self.max_len // self._page_size,), np.int32))
                with self._compile.site(self._site("slot_insert")):
                    self.cache = self._insert(self.cache, row_cache, bt_row,
                                              slot0)
                for b in self.buckets:
                    with self._compile.site(self._site(f"extend[b{b}]")):
                        self.cache, _ = self._extend(
                            self.params, self.cache, slot0, bt_row,
                            jnp.zeros((1, b), jnp.int32),
                            jnp.asarray(0, jnp.int32),
                            jnp.asarray(1, jnp.int32))
            else:
                with self._compile.site(self._site("slot_insert")):
                    self.cache = self._insert(self.cache, row_cache, slot0)
        inactive = self._dev(np.zeros((self.slots,), bool))
        if self.role != "prefill":
            # a prefill-role engine never dispatches a decode/verify
            # window — the per-role census pins zero window programs there
            temps0 = self._dev(np.zeros((self.slots,), np.float32))
            topps0 = self._dev(np.zeros((self.slots,), np.float32))
            topks0 = self._dev(np.zeros((self.slots,), np.int32))
            minps0 = self._dev(np.zeros((self.slots,), np.float32))
            keys0 = self._dev(np.zeros((self.slots, 2), np.uint32))
            pos0 = self._dev(np.zeros((self.slots,), np.int32))
            if self._verify is not None:
                k = self.draft_len + 1
                with self._compile.site(self._site(f"verify_window[k{k}]")):
                    self.cache, _, _, _, _ = self._verify(
                        self.params, self.cache,
                        self._dev(np.full((self.slots, k), self.pad_id,
                                          np.int32)),
                        self._dev(np.zeros((self.slots,), np.int32)),
                        inactive, temps0, topps0, topks0, minps0, keys0,
                        pos0)
            else:
                k = self.decode_ahead
                with self._compile.site(self._site(f"decode_window[k{k}]")):
                    self.cache, _, _, _, _ = self._window(
                        self.params, self.cache,
                        self._dev(np.zeros((self.slots,), np.int32)),
                        inactive, temps0, topps0, topks0, minps0, keys0,
                        pos0)
        with self._compile.site(self._site("slot_reset")):
            self.cache = self._reset(self.cache, inactive)
        delta = CompileTracker.delta(self._compile.snapshot(), before)
        return {"programs": delta["n_compiled_programs"],
                "compile_s": delta["compile_time_s"],
                "wall_s": round(self.clock() - t0, 6),
                "by_site": delta["by_site"]}
