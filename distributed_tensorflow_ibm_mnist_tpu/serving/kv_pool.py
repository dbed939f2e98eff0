"""Paged KV-cache pool: the serving-side half of the paged decode path.

The dense engine allocates ``slots * max_len`` cache positions up front, so
concurrency is capped by worst-case-length allocation even when every live
request is short.  This module re-blocks the cache into a fixed pool of
``page_size``-token pages per layer — ``(n_pages, page_size, H_kv, D)``
pytree leaves — plus a per-slot ``(max_len / page_size,)`` BLOCK TABLE
mapping each row's virtual positions to pool pages.  Memory then scales
with LIVE tokens: admission allocates ``ceil((len + max_new) / page_size)``
pages, retirement frees them, and the engine can run more slots than the
pool could hold at worst case (overcommit), stalling admission — never
corrupting — when the pool is momentarily full.

Layout contract (mirrors the dense cache per block name):

    dense   {"k": (B, max_len, hkv, d), "v": ..., ["k_scale"/"v_scale":
             (B, max_len, hkv)], "index": (B,)}
    paged   {"pages_k": (n_pages, ps, hkv, d), "pages_v": ...,
             ["pages_k_scale"/"pages_v_scale": (n_pages, ps, hkv)],
             "block_table": (B, max_len // ps) int32, "index": (B,)}

A model whose layers keep different kinds of state (models/sala.py) says
itself what each layer holds (``model.paged_cache_shapes``): a sparse layer
the paged entry above plus ``kc`` (B, max_len / stride, hkv, d) float32
compressed keys, a lightning layer ``{"state": (B, H, d, d) float32,
"index": (B,)}`` and no pages at all.  A sliding-window layer
(models/mimo.py) likewise owns NO page: the last ``window`` positions of a
row are all it ever reads, so it keeps them in a ring a row, ``ring_k`` /
``ring_v`` (B, window, hkv, d), position p at slot p mod window — nothing is
freed "as the window slides" because nothing was allocated; pages are
allocated for the global layers alone, whose ``pages_k`` and ``pages_v``
may differ in width.  ``ROW_LEAVES`` names those per-row leaves,
``SHARED_LEAVES`` the small whole leaves a chunk updates as it does the
pool; every function here takes an entry as it finds it.

Page 0 is a reserved TRASH page: every unallocated block-table entry points
at it, so idle rows' decode writes land in garbage nobody reads (the model's
causal mask only exposes positions below a live row's cursor, all of which
lie in allocated pages).  ``KVPagePool`` is the host-side allocator over
pages ``1 .. n_pages-1``; page ids are shared across layers (page ``p``
means slab ``p`` in EVERY layer's pool), which is what lets the radix
prefix cache (serving/radix_cache.py) refcount a whole-model prefix block
as one integer.

Reads of the pool take two forms (models/transformer.py
``_paged_decode_attention``): multi-token chunks, int8 pools and tp/cp-
sharded pools gather a row's whole virtual span ``pool[block_table]``; a
single-token step of a one-device engine over a compute-dtype pool with
head dim 128 runs the ops/paged_attention.py kernel, which walks a row's
block table only as far as its cursor and never dereferences an entry
past it.  The kernel reads the leaves exactly as laid out above (one
``(hkv, d)`` tile per token, viewed in place as 32-bit rows), so the layout
contract is unchanged and there is ONE definition of it, here.

Everything jitted here is donation-friendly: the engine wraps
``make_paged_insert``/``paged_reset``/``make_paged_extend`` in ``jax.jit``
with the cache donated, same as the dense path (the ~23% donation win from
PR 2 carries over — the pool is the dominant buffer either way).

Context parallelism (ISSUE 20) never touches this module's code: under a
``cp > 1`` serving mesh the engine shards every ``pages_*`` leaf along its
PAGE axis (``kv_cache_rule`` pins ``P("cp", None, head, None)``), so each
of the ``cp`` chip rows physically holds ``n_pages / cp`` page slabs —
1/cp of the live KV bytes — while the block table and ``KVPagePool``
keep addressing the same GLOBAL page ids.  The (chip, page) split is the
partitioner's business: an insert's page write lands on whichever chip
row owns the target slab, decode's per-row gather assembles the attended
span across rows, and the host-side allocator, radix refcounts, and
trash-page protocol are layout-invariant — the same integers mean the
same pages at any cp.  The only cp-visible constraint lives in the
engine: ``n_pages`` must divide by ``cp`` so the page axis shards evenly.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..core.generate import _zeros_like_shapes

TRASH_PAGE = 0
# leaves whose leading axis is the SLOT and whose size does not grow with a
# row's length — a lightning layer's recurrent ``state``, a sparse layer's
# compressed keys ``kc`` (models/sala.py).  They live beside the page pool
# in the same cache tree and are owned by the same programs: a prefill chunk
# narrows them to its row and writes the row back; a row's first chunk
# starts them from nothing, so reset has no work to do on them.  A window
# layer's ring of its last ``window`` keys and values (models/mimo.py), a
# Mamba layer's state and its convolution's last inputs
# (models/nemotron_h.py) are the same kind of leaf
ROW_LEAVES = ("state", "kc", "ring_k", "ring_v", "ssm_state", "conv_state")
# leaves that belong to no row and no page: a chunk takes them whole and
# hands them back whole, like the pool (an expert layer's load counter, and
# the ids the last chunk's tokens chose where models/nemotron_h.py keeps them)
SHARED_LEAVES = ("expert_load", "chunk_chosen")


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages to hold ``n_tokens`` cache positions (host-side ceil div)."""
    return -(-int(n_tokens) // int(page_size))


class KVPagePool:
    """Host-side page allocator over a pool of ``n_pages`` pages.

    Page 0 is the reserved trash page and is never handed out.  ``alloc``
    is all-or-nothing (a partially admitted request would deadlock the
    pool) and hands out the lowest free ids first — deterministic, so the
    paged engine's behaviour replays exactly under the fault-injection
    harness.  The allocator knows nothing about sharing: the radix cache
    owns refcounts and calls ``free`` only when a page's count reaches
    zero.
    """

    def __init__(self, n_pages: int, page_size: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the reserved trash page), "
                f"got {n_pages}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # pop() takes from the END: store descending so allocation walks
        # ascending page ids (determinism + readable block tables)
        self._free = list(range(self.n_pages - 1, 0, -1))

    @property
    def capacity(self) -> int:
        """Allocatable pages (the trash page excluded)."""
        return self.n_pages - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def allocated(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` pages, or None (and take nothing) if fewer are free."""
        if n < 0:
            raise ValueError(f"alloc needs n >= 0, got {n}")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        return out

    def free(self, pages) -> None:
        """Return pages to the pool.  Accepts any iterable of page ids."""
        for p in pages:
            p = int(p)
            if not 0 < p < self.n_pages:
                raise ValueError(
                    f"free of invalid page id {p} (pool has pages 1.."
                    f"{self.n_pages - 1}; page 0 is reserved)")
            self._free.append(p)
        if len(self._free) > self.capacity:
            raise ValueError("double free: more pages freed than exist")


def init_paged_cache(model, params, slots: int, max_len: int,
                     page_size: int, n_pages: int, shardings=None):
    """A zeroed paged decode cache for ``model``: per-layer page pools
    sized ``n_pages`` plus per-slot block tables and cursors, derived from
    the DENSE decode layout via ``jax.eval_shape`` (no forward runs), so
    dtypes — including the int8 payload + f32 scale split — always match
    what the dense path would have stored.

    ``model`` may be the dense model or its paged clone; the dense layout
    is probed either way.  Every block table starts all-TRASH (page 0) and
    every cursor at 0 — the state ``paged_reset`` restores per slot.

    ``shardings`` (a pytree of shardings matching the returned cache
    structure) allocates each pool leaf directly in its sharded layout, so
    a pool bigger than one chip never materializes on a single device.
    """
    return _zeros_like_shapes(
        paged_cache_shapes(model, params, slots, max_len, page_size,
                           n_pages), shardings)


def paged_cache_shapes(model, params, slots: int, max_len: int,
                       page_size: int, n_pages: int):
    """ShapeDtypeStruct tree of the paged cache :func:`init_paged_cache`
    allocates — exposed (like ``core.generate.cache_shapes``) so the
    tensor-parallel engine can derive a congruent sharding tree before
    any pool memory exists."""
    if max_len % page_size:
        raise ValueError(
            f"max_len ({max_len}) must be a multiple of page_size "
            f"({page_size}) so each slot's virtual span is exactly max_len")
    if n_pages < 2:
        raise ValueError(f"n_pages must be >= 2, got {n_pages}")
    if hasattr(model, "paged_cache_shapes"):
        # a model whose layers keep different kinds of state says itself
        # what each layer holds (it has no dense layout to derive it from)
        return model.paged_cache_shapes(slots, max_len, page_size, n_pages)
    dense = model.clone(page_size=0) if getattr(model, "page_size", 0) else model
    shapes = jax.eval_shape(
        lambda p: dense.apply(
            {"params": p}, jnp.zeros((slots, 1), jnp.int32),
            decode=True, max_len=max_len, ragged=True, mutable=["cache"],
        )[1]["cache"],
        params,
    )
    n_row = max_len // page_size
    struct = jax.ShapeDtypeStruct
    paged_shapes = {}
    for name, entry in shapes.items():
        k = entry["k"]  # (slots, max_len, hkv, d)
        hkv, d = k.shape[2], k.shape[3]
        paged = {
            "pages_k": struct((n_pages, page_size, hkv, d), k.dtype),
            "pages_v": struct((n_pages, page_size, hkv, d),
                              entry["v"].dtype),
            "block_table": struct((slots, n_row), jnp.int32),
            "index": struct((slots,), jnp.int32),
        }
        if "k_scale" in entry:
            paged["pages_k_scale"] = struct(
                (n_pages, page_size, hkv), entry["k_scale"].dtype)
            paged["pages_v_scale"] = struct(
                (n_pages, page_size, hkv), entry["v_scale"].dtype)
        paged_shapes[name] = paged
    return paged_shapes


def pool_page_bytes(cache) -> int:
    """Bytes one page occupies across every layer's pool leaves — the
    ``kv_bytes_live = pages_live * pool_page_bytes`` accounting unit."""
    total = 0
    for entry in cache.values():
        for key, leaf in entry.items():
            if key.startswith("pages_"):
                total += leaf.nbytes // leaf.shape[0]
    return total


def make_paged_insert(page_size: int, max_len: int) -> Callable:
    """Build ``insert(cache, row_cache, bt_row, slot) -> cache``: write a
    dense prefilled B=1 row (make_prefill's layout) into the page pool
    through ``bt_row`` and install the row's block table + cursor at
    ``slot``.  The engine jits this with the cache donated.

    Only the pages under the row's cursor are written, each as one whole
    page: ``row["index"]`` is the prompt's real length, so the row has
    ``ceil(index / page_size)`` live pages, and page ``j`` of the row's
    ``(max_len / page_size, page_size, ...)`` view (a free reshape)
    belongs at ``pages[bt_row[j]]`` as one contiguous block.  One loop
    over the live pages carries every pool leaf and updates it in place:
    the work follows the prompt, the program is one whatever the bucket.
    The last live page takes the row's padding above the cursor with it,
    and the allocation's pages beyond it keep what their last tenant
    left: no position at or above a cursor is read unmasked, and a decode
    step writes its position before any step reads it.  Whole-page writes
    are safe because a dense-prefilled request owns ALL of its pages
    privately (pages become shared only by donation to the radix trie
    AFTER insert, and donated pages are read-only from then on: later
    tenants of the same prefix never write below their cursor).
    """
    n_row = max_len // page_size

    def insert(cache, row_cache, bt_row, slot):
        pools = pool_page_leaves(cache)
        rows = {
            name: {key: row_cache[name][key.removeprefix("pages_")][0].reshape(
                (n_row, page_size) + leaf.shape[2:])
                for key, leaf in entry.items()}
            for name, entry in pools.items()}
        n_tok = next(iter(row_cache.values()))["index"][0]

        def write_page(j, pools):
            return jax.tree.map(
                lambda pool, row: jax.lax.dynamic_update_slice_in_dim(
                    pool,
                    jax.lax.dynamic_slice_in_dim(row, j, 1).astype(pool.dtype),
                    bt_row[j], axis=0),
                pools, rows)

        pools = jax.lax.fori_loop(
            0, (n_tok + page_size - 1) // page_size, write_page, pools)
        out = {}
        for name, entry in cache.items():
            e = {**entry, **pools[name]}
            e["block_table"] = jax.lax.dynamic_update_slice(
                entry["block_table"], bt_row[None].astype(jnp.int32),
                (slot, 0))
            e["index"] = jax.lax.dynamic_update_slice(
                entry["index"],
                row_cache[name]["index"].astype(entry["index"].dtype),
                (slot,))
            out[name] = e
        return out

    return insert


def paged_reset(cache, slot_mask):
    """Per-slot reset in the paged layout: point the masked slots' block
    tables back at the trash page and zero their cursors.  The POOL is
    untouched — a freed page's stale K/V is dead data (nothing maps to it)
    until the allocator hands the page to a new tenant, which writes every
    position before its mask exposes it: the insert the pages under the
    prompt, extend and decode each position as the cursor reaches it.  The
    paged sibling of models/transformer.py ``reset_cache_slots``; the
    engine jits it with the cache donated under the same compile site.
    """
    mask = jnp.asarray(slot_mask, bool)
    out = {}
    for name, entry in cache.items():
        e = dict(entry)
        if "block_table" in entry:  # a state-only layer has none
            e["block_table"] = jnp.where(
                mask[:, None], TRASH_PAGE, entry["block_table"])
        e["index"] = jnp.where(mask, 0, entry["index"])
        out[name] = e
    return out


def pool_page_leaves(cache):
    """The ``pages_*`` leaves of a paged cache as a congruent sub-tree —
    the payload layout one page occupies across every layer (the handoff
    transfer unit, serving/kv_handoff.py)."""
    return {name: {k: v for k, v in entry.items() if k.startswith("pages_")}
            for name, entry in cache.items()}


def gather_page(cache, page_id):
    """One page's cross-layer payload: ``{layer: {pages_k: (ps, hkv, d),
    ...}}`` sliced at ``page_id``.  Read-only (jit WITHOUT donation — the
    source pool stays live until the handoff commits); ``device_get`` of
    the result assembles shards host-side, which is what makes a tp=4
    prefill pool's head-sharded page land as one full host array for a
    tp=1 decode pool (the resharding seam of the disaggregated tier)."""
    return jax.tree.map(lambda leaf: leaf[page_id], pool_page_leaves(cache))


def page_write(cache, payload, page_id):
    """Scatter one page's cross-layer ``payload`` (the
    :func:`gather_page` tree, host- or device-resident) into page
    ``page_id`` of every layer's pool.  Fixed shape at ANY prompt length
    — the handoff installs N pages as N dispatches of this ONE program,
    so the per-role compile census never moves with traffic.  The engine
    jits this with the cache donated."""
    out = {}
    for name, entry in cache.items():
        e = dict(entry)
        for key in entry:
            if key.startswith("pages_"):
                e[key] = entry[key].at[page_id].set(
                    payload[name][key].astype(entry[key].dtype))
        out[name] = e
    return out


def bt_install(cache, bt_row, slot, cursor):
    """Install ``slot``'s block table row and cursor across every layer —
    the no-forward landing step of a handed-off request (its K/V pages
    are already in the pool; only the mapping and the cursor are new).
    The engine jits this with the cache donated."""
    out = {}
    for name, entry in cache.items():
        e = dict(entry)
        e["block_table"] = jax.lax.dynamic_update_slice(
            entry["block_table"], bt_row[None].astype(jnp.int32), (slot, 0))
        e["index"] = entry["index"].at[slot].set(
            jnp.asarray(cursor, jnp.int32))
        out[name] = e
    return out


def make_paged_extend(model, max_len: int, page_size: int) -> Callable:
    """Build the PARTIAL-PREFIX prefill program: ``extend(params, cache,
    slot, bt_row, suffix, start, suffix_len) -> (cache, last_logits)``.

    When the radix cache matches the first ``start`` tokens of a prompt
    (whole shared pages), only the unshared suffix needs computing.  The
    suffix runs as ONE decode-mode chunk over the slot's block table: its
    queries attend the shared pages (read-only) plus themselves, and its
    K/V scatter into the slot's PRIVATE pages — copy-on-write at the
    divergence page falls out of the layout, because the block table remaps
    the diverging virtual block to a private page and the shared page is
    never written.  ``suffix`` is (1, Sb) right-padded to a bucket length;
    positions above ``suffix_len`` write garbage above the cursor into
    private pages (masked, later overwritten by decode).  The cursor is set
    to ``start + suffix_len`` (the REAL length, not the padded one) and
    ``last_logits`` is (1, V) at the last real suffix position — pick the
    first generated token from it, exactly like a dense prefill.

    ``model`` must be the PAGED clone (``page_size > 0``).  The engine jits
    this with the cache donated.
    """
    if not getattr(model, "page_size", 0):
        raise ValueError(
            "make_paged_extend needs the paged model clone "
            "(model.page_size > 0) — it decodes through the page pool")
    n_row = max_len // page_size
    # a model with per-row state is told how many of the chunk's tokens
    # are real (its state must not absorb the padding)
    counts_valid = bool(getattr(model, "has_recurrent_state", False))

    def extend(params, cache, slot, bt_row, suffix, start, suffix_len):
        # install the row's block table first: the chunk decodes through it
        cache = {
            name: {
                **e,
                "block_table": jax.lax.dynamic_update_slice(
                    e["block_table"], bt_row[None].astype(jnp.int32),
                    (slot, 0)),
            } if "block_table" in e else e
            for name, e in cache.items()
        }
        # B=1 sub-cache over the FULL pool: only the slot's table row,
        # cursor and per-row leaves narrow to the row; the pool leaves are
        # shared storage
        sub = {}
        for name, e in cache.items():
            se = {k: v for k, v in e.items()
                  if k.startswith("pages_") or k in SHARED_LEAVES}
            if "block_table" in e:
                se["block_table"] = jax.lax.dynamic_slice(
                    e["block_table"], (slot, 0), (1, n_row))
            for k in ROW_LEAVES:
                if k in e:
                    se[k] = jax.lax.dynamic_slice_in_dim(e[k], slot, 1, axis=0)
            se["index"] = jnp.zeros((1,), jnp.int32) + start
            if counts_valid:
                se["n_valid"] = jnp.zeros((1,), jnp.int32) + suffix_len
            sub[name] = se
        logits, vars_ = model.apply(
            {"params": params, "cache": sub}, suffix.astype(jnp.int32),
            decode=True, max_len=max_len, ragged=True, mutable=["cache"],
        )
        new = vars_["cache"]
        out = {}
        for name, e in cache.items():
            oe = dict(e)
            for key in e:
                if key.startswith("pages_") or key in SHARED_LEAVES:
                    oe[key] = new[name][key]
                elif key in ROW_LEAVES:
                    oe[key] = jax.lax.dynamic_update_slice_in_dim(
                        e[key], new[name][key], slot, axis=0)
            # real cursor, not the padded chunk's clamped one
            oe["index"] = e["index"].at[slot].set(
                (start + suffix_len).astype(jnp.int32))
            out[name] = oe
        last = jax.lax.dynamic_index_in_dim(
            logits[0], suffix_len - 1, axis=0, keepdims=False)  # (V,)
        return out, last[None]

    return extend
