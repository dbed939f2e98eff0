"""Paged-KV handoff: moving a finished prefill between engines (ISSUE 16).

The disaggregated tier splits the two phases of serving a request across
role-typed replicas: PREFILL replicas run the prompt through the
prefill/extend program family and stop at the moment every monolithic
engine would pick the first token; DECODE replicas run the decode window
and never compile a prefill bucket.  This module is the seam between
them — the packaging of a finished prefill into a host-side
:class:`HandoffPacket` and its landing on a decode engine — with three
invariants the chaos suite gates on:

* **Deferred source-free.**  The packet carries the source slot's page
  HOLD (private pool pages + acquired radix nodes) and nothing frees
  until the router confirms delivery (:meth:`HandoffPacket.release`).  A
  transfer that dies in flight (the ``kv-handoff`` chaos site) releases
  the hold and re-dispatches the request down the normal prefill path —
  the source trie still has the prompt's shared blocks, so the retry's
  re-prefill is a radix hit, and the router's delivered high-water mark
  keeps the replay exactly-once per token.
* **All-or-nothing landing.**  :func:`deliver` allocates the request's
  FULL destination page span before touching the destination cache; a
  dry pool returns False with zero writes issued (the router re-parks
  the packet and retries next pump — admission stall semantics, never
  corruption).  Failures after allocation are the request's own and
  reclaim every destination page.
* **Radix-aware arrival.**  The destination trie is matched before the
  scatter: blocks it already holds are acquired and mapped into the
  block table WITHOUT re-uploading their payload (shared-prefix pages
  dedup on arrival), and freshly landed full prompt blocks are donated
  back so the NEXT handoff of the same prefix skips them too.

Resharding falls out of the host hop: :func:`~.kv_pool.gather_page` is
jitted read-only on the SOURCE mesh and ``jax.device_get`` assembles its
shards into one full host array, which the DESTINATION engine re-uploads
through its own ``_dev`` commitment — a tp=4 prefill pool's head-sharded
page lands on a tp=1 decode pool (or any other degree) with no
device-to-device protocol and no extra program.

Census discipline: the transfer unit is ONE page, so a prompt of any
length moves as N dispatches of the same two fixed-shape programs
(``handoff_gather`` on the source, the per-page writer + no-forward
``bt_install`` under ``handoff_install`` on the destination) — the
per-role compile census never moves with traffic, which is what
tests/test_disagg.py::test_per_role_prewarm_census pins.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_ibm_mnist_tpu.serving.kv_pool import pages_needed


@dataclasses.dataclass
class HandoffPacket:
    """One finished prefill, portable across engines.

    ``req`` is the SAME engine :class:`~.scheduler.Request` object the
    source admitted — its router-wrapped callback (and therefore the
    cross-attempt delivered high-water mark) travels with it, so the
    decode side's tokens stream through the identical exactly-once path
    a monolithic engine's would.  ``payloads`` holds one host tree per
    CONTENT page (the :func:`~.kv_pool.gather_page` layout, prompt pages
    only — decode-span pages are garbage by contract and never move);
    ``last_logits`` is the prefill's (1, V) float32 last-position row,
    from which the destination picks the first token through the shared
    ``first_pick`` program — bit-identical to the pick the source would
    have made.  ``hold`` is the source-side page hold released only at
    :meth:`release` (deferred source-free; module docstring).
    """

    req: Any
    n_tok: int
    payloads: list
    last_logits: np.ndarray
    source: Any                      # the source InferenceEngine
    hold: list | None                # [private page ids, held radix nodes]
    created_t: float
    gather_s: float
    payload_bytes: int
    trace_ctx: Any = None            # distributed TraceContext riding the
    #   handoff: the prefill-role and decode-role spans of one request
    #   join under trace_ctx.trace_id even when the roles run on separate
    #   tracers (the merged export connects them via span_ctx/parent_ctx)

    def release(self) -> None:
        """Free the source-side hold — called by the router exactly when
        the packet is consumed (delivered, or abandoned to a re-dispatch
        after a transfer fault).  Idempotent; a closed/dead source engine
        is a no-op (its pool died with it)."""
        hold, self.hold = self.hold, None
        if hold is None:
            return
        src = self.source
        if src is None or getattr(src, "_closed", False):
            return
        pages, nodes = hold
        if pages:
            src._pool.free(pages)
        if nodes:
            src._radix.release(nodes)


def package(engine, req, slot: int, logits_dev, bt_row) -> "HandoffPacket":
    """Source half: gather ``slot``'s prompt pages to the host and build
    the packet.  Called by the prefill-role engine at the exact point
    every other landing path would run ``first_pick`` — the slot's page
    hold transfers to the packet (the caller clears the slot and queues
    its block-table reset; the PAGES stay allocated until
    :meth:`HandoffPacket.release`).

    Gathers are read-only (no donation), so a fault anywhere in here
    leaves the source cache untouched: the caller's failure path reclaims
    the allocation exactly as for any admission-tail exception.
    """
    t0 = engine.clock()
    ps = engine._page_size
    n_tok = int(req.tokens.size)
    n_blocks = pages_needed(n_tok, ps)
    payloads = []
    for j in range(n_blocks):
        with engine._compile.site("handoff_gather"):
            payloads.append(jax.device_get(engine._page_gather(
                engine.cache, jnp.asarray(int(bt_row[j]), jnp.int32))))
    last = np.asarray(jax.device_get(logits_dev), np.float32)
    nbytes = sum(leaf.nbytes for p in payloads
                 for leaf in jax.tree.leaves(p)) + last.nbytes
    t1 = engine.clock()
    # the hold moves LAST, after every gather succeeded — an exception
    # above leaves it on the slot for _release_slot_alloc to reclaim
    hold = engine._slot_alloc[slot]
    engine._slot_alloc[slot] = None
    if req.admit_t is None:
        req.admit_t = t0
    req.status = "prefilled"
    engine._tr_phase(req, "handoff", slot=slot, pages=n_blocks)
    if engine._tracer is not None and req.trace is not None:
        engine._tracer.complete(
            "gather", t0, t1, cat="handoff",
            parent=req.trace.get("phase") or req.trace["id"],
            tid=req.trace["tid"], pages=n_blocks, bytes=int(nbytes))
    engine._last_progress_ever = t1
    return HandoffPacket(req=req, n_tok=n_tok, payloads=payloads,
                         last_logits=last, source=engine, hold=hold,
                         created_t=t0, gather_s=t1 - t0,
                         payload_bytes=int(nbytes),
                         trace_ctx=req.trace_ctx)


def deliver(engine, packet: "HandoffPacket") -> bool:
    """Destination half: land ``packet`` on a decode-capable engine.

    Returns True when the packet was CONSUMED — landed and decoding, or
    failed on its own admission tail (the request is terminal either
    way) — and False when the engine cannot take it RIGHT NOW (no free
    slot, or the all-or-nothing destination allocation found the pool
    dry): a False return issued zero cache writes, so the router re-parks
    the packet and retries after decode frees capacity.
    """
    req = packet.req
    slot = next((i for i in range(engine.slots)
                 if engine._slot_req[i] is None), None)
    if slot is None:
        return False
    now = engine.clock()
    ps = engine._page_size
    n_tok = packet.n_tok
    # radix dedup on arrival: full prompt blocks the destination trie
    # already shares need no payload upload — map them straight into the
    # block table (acquired first, so allocation cannot evict them)
    path: list = []
    if engine._radix is not None:
        path, _matched = engine._radix.match(req.tokens)
    m_blocks = len(path)
    if m_blocks:
        engine._radix.acquire(path)
    total = pages_needed(n_tok + req.max_new, ps)
    private = engine._alloc_pages(total - m_blocks)
    if private is None:
        if m_blocks:
            engine._radix.release(path)
        return False
    engine._slot_alloc[slot] = [list(private), list(path)]
    bt_row = np.zeros((engine.max_len // ps,), np.int32)
    for j, node in enumerate(path):
        bt_row[j] = node.page
    for j, page in enumerate(private):
        bt_row[m_blocks + j] = page
    try:
        t0 = engine.clock()
        n_blocks = pages_needed(n_tok, ps)
        for j in range(m_blocks, n_blocks):
            with engine._compile.site("handoff_install"):
                payload = jax.tree.map(engine._dev, packet.payloads[j])
                engine.cache = engine._page_write(
                    engine.cache, payload,
                    jnp.asarray(int(bt_row[j]), jnp.int32))
        with engine._compile.site("handoff_install"):
            engine.cache = engine._bt_install(
                engine.cache, engine._dev(bt_row),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(n_tok, jnp.int32))
        t1 = engine.clock()
        if engine._tracer is not None and req.trace is not None:
            engine._tracer.complete(
                "install", t0, t1, cat="handoff",
                parent=req.trace.get("phase") or req.trace["id"],
                tid=req.trace["tid"], pages=n_blocks - m_blocks,
                dedup_pages=m_blocks, slot=slot)
        if engine._radix is not None:
            engine.stats.radix(m_blocks > 0, tokens=m_blocks * ps)
            engine._radix.record(m_blocks > 0, tokens=m_blocks * ps)
            donate = {j: int(bt_row[j])
                      for j in range(m_blocks, n_tok // ps)}
            if donate:
                priv, nodes = engine._slot_alloc[slot]
                held, _kept = engine._radix.insert(
                    req.tokens, m_blocks, donate, path)
                for node in held:
                    priv.remove(node.page)
                    nodes.append(node)
        req.pages = total
        # first token: the source's logits row through the SAME shared
        # pick program every landing path uses — bit-identical to the
        # token a monolithic engine would have picked, which is what
        # tests/test_disagg.py's disagg-vs-monolithic token parity checks
        first, first_logp = engine._first_pick(
            req, engine._dev(packet.last_logits))
        req.generated.append(first)
        req.logprobs.append(first_logp)
        req.first_token_t = engine.clock()
        engine._last_progress_ever = req.first_token_t
        if req.ttft_slo_s is not None:
            req.slo_ttft_ok = (
                req.first_token_t - req.submit_t <= req.ttft_slo_s)
        if engine._telemetry is not None:
            engine._telemetry.observe(
                "ttft_s", req.first_token_t - req.submit_t,
                exemplar=(packet.trace_ctx.trace_id
                          if packet.trace_ctx is not None else None))
            engine._telemetry.inc("tokens_generated")
        req.status = "running"
        engine._tr_phase(req, "decode", slot=slot, handoff=True)
        engine._tr_instant(req, "first_token", slot=slot,
                           cache_hit=False)
        engine._notify(req, first)
    except Exception as e:
        # the request's OWN failure (poisoned callback and kin): reclaim
        # the destination pages, reset the (possibly installed) row, and
        # report the packet consumed — terminal, not re-parkable
        engine._release_slot_alloc(slot)
        engine._fail(req, e, engine.clock())
        engine._reset_slot_now(slot)
        return True
    engine._slot_req[slot] = req
    engine._slot_tok[slot] = first
    temp, topp, topk, minp, key = engine._req_sampling(req)
    engine._slot_temp[slot] = temp
    engine._slot_topp[slot] = topp
    engine._slot_topk[slot] = topk
    engine._slot_minp[slot] = minp
    engine._slot_key[slot] = key
    engine._tok_dev = None
    engine._active_dev = None
    engine._planes_dev = None
    engine._pos_dev = None
    engine.stats.prompt_admitted(n_tok)
    engine.handoffs_in += 1
    if req.admit_t is None:
        req.admit_t = now
    if engine._done_reason(req) is not None:
        engine._retire(slot, engine._done_reason(req), engine.clock())
        engine._reset_slot_now(slot)
    return True
