"""Crash durability (serving/journal.py) — the write-ahead request
journal and whole-process recovery.

The decisive properties (ISSUE 18):

* WAL ORDER — ``admitted`` is on disk before ``submit()`` returns; a
  raising append fails the submit (no ack without the WAL behind it);
  ``delivered`` never overstates what the client received.
* TORN-TAIL TOLERANCE — truncated final record, bit-flipped checksum,
  empty segment, missing segment: the scan drops exactly what cannot be
  trusted (``records_dropped``), flags the crash signature
  (``torn_tail``), surfaces gaps, and recovery proceeds on the rest.
* EXACTLY-ONCE ACROSS THE CRASH — ``recover()`` re-submits every
  incomplete request with ``resume_from=<delivered high-water>``; the
  deterministic stream (PR 13) re-derives identical tokens, so the
  stitched transcript (delivered prefix + replayed suffix) is
  token-identical to an uncrashed reference, no gaps, no duplicates.
* CHAOS — the ``journal-write`` site's torn/corrupt/io kinds produce
  exactly the on-disk damage the scan is built for.
"""

import json
import os
import random
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler,
    InferenceEngine,
    JournalWriteError,
    RequestJournal,
    Router,
    SamplingParams,
    ServingDaemon,
    recover,
    scan_journal,
    transcript_digest,
)
from distributed_tensorflow_ibm_mnist_tpu.serving.daemon import DaemonRequest
from distributed_tensorflow_ibm_mnist_tpu.serving.journal import (
    _encode,
    _segment_name,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.chaos import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)

KW = dict(num_classes=16, dim=32, depth=1, heads=2, dtype=jnp.float32)
PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
WAIT_S = 120.0


@pytest.fixture(scope="module")
def model_and_params():
    model = get_model("causal_lm", **KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _factory(model, params, max_len=16, **kw):
    def make_engine(tid):
        return InferenceEngine(
            model, params, slots=2, max_len=max_len,
            scheduler=FIFOScheduler(max_len=max_len, buckets=(8,),
                                    max_queue=16),
            trace_tid=tid, **kw)
    return make_engine


def _reference(model, params, prompts=PROMPTS, max_new=6, sampling=None):
    eng = InferenceEngine(model, params, slots=2, max_len=16,
                          scheduler=FIFOScheduler(max_len=16, buckets=(8,)))
    reqs = [eng.submit(p, max_new=max_new, sampling=sampling)
            for p in prompts]
    eng.run()
    eng.close()
    return [list(r.generated) for r in reqs]


def _fake_dr(rid, prompt=(1, 2, 3), max_new=4, **kw):
    """A DaemonRequest the journal can serialize without a live tier."""
    dr = DaemonRequest(rid, list(prompt), max_new,
                       deadline_s=kw.pop("deadline_s", 30.0),
                       submit_t=0.0, callback=None, **kw)
    dr.fingerprint = "00" * 8
    return dr


# ----------------------------------------------------------------------
# write side


def test_journal_roundtrip_rotation_and_fresh_segments(tmp_path):
    """Records round-trip through the checksummed segment files; tiny
    ``segment_bytes`` forces rotation; a second writer over the same
    directory never reopens an existing segment."""
    d = str(tmp_path / "j")
    with RequestJournal(d, fsync_policy="never", segment_bytes=200) as j:
        for i in range(4):
            j.admitted(_fake_dr(i))
        j.delivered(0, 2)
        j.delivered(0, 3)           # high-water moves forward
        j.retired(0, "done", None)
        j.retired(1, "failed", "boom")
    st = j.stats()
    assert st["records"] == 8
    assert st["by_type"] == {"admitted": 4, "delivered": 2, "retired": 2}
    assert st["rotations"] >= 2     # 200-byte segments can't hold it all
    assert st["errors"] == 0

    scan = scan_journal(d)
    assert scan.records == 8
    assert scan.records_dropped == 0 and not scan.torn_tail
    assert scan.segment_gaps == [] and scan.orphan_records == 0
    assert sorted(scan.requests) == [0, 1, 2, 3]
    assert scan.requests[0] == {"meta": scan.requests[0]["meta"],
                                "delivered": 3, "retired": "done"}
    assert scan.requests[1]["retired"] == "failed"
    assert [s["meta"]["id"] for s in scan.incomplete()] == [2, 3]
    rep = scan.report()
    assert rep["requests"] == 4 and rep["retired"] == 2
    assert rep["incomplete"] == 2

    # a fresh writer starts PAST every existing segment
    first_segments = set(scan.segments)
    with RequestJournal(d, fsync_policy="never") as j2:
        j2.retired(2, "cancelled", None)
    scan2 = scan_journal(d)
    new = set(scan2.segments) - first_segments
    assert len(new) == 1            # one new segment, none reopened
    assert scan2.requests[2]["retired"] == "cancelled"

    # meta preserves the full identity recovery needs
    meta = scan2.requests[3]["meta"]
    assert meta["prompt"] == [1, 2, 3] and meta["max_new"] == 4
    assert meta["fp"] == "00" * 8 and "wall_t" in meta


def test_journal_fsync_policies(tmp_path):
    """Policy validation + the fsync ledger: ``always`` pays one fsync
    per append, ``never`` only the final close-fsync."""
    with pytest.raises(ValueError):
        RequestJournal(str(tmp_path / "x"), fsync_policy="sometimes")
    with pytest.raises(ValueError):
        RequestJournal(str(tmp_path / "x"), fsync_interval_s=0)
    with pytest.raises(ValueError):
        RequestJournal(str(tmp_path / "x"), segment_bytes=0)

    ja = RequestJournal(str(tmp_path / "a"), fsync_policy="always")
    for i in range(5):
        ja.delivered(0, i)
    ja.close()
    assert ja.stats()["fsyncs"] >= 5

    jn = RequestJournal(str(tmp_path / "n"), fsync_policy="never")
    for i in range(5):
        jn.delivered(0, i)
    jn.close()
    assert jn.stats()["fsyncs"] == 1    # close() always syncs

    jn.close()                          # idempotent
    with pytest.raises(JournalWriteError):
        jn.delivered(0, 9)              # closed journal refuses appends


# ----------------------------------------------------------------------
# read side: corruption tolerance


def _write_clean(d, n_requests=6, segment_bytes=300):
    j = RequestJournal(d, fsync_policy="never", segment_bytes=segment_bytes)
    for i in range(n_requests):
        j.admitted(_fake_dr(i))
        j.delivered(i, 2)
    j.retired(0, "done", None)
    j.close()
    return j.stats()["records"]


def test_scan_truncated_tail(tmp_path):
    """A torn final record — the crash-mid-append signature — is dropped
    alone and flagged ``torn_tail``; every earlier record survives."""
    d = str(tmp_path / "j")
    total = _write_clean(d)
    segs = sorted(f for f in os.listdir(d) if f.startswith("journal-"))
    path = os.path.join(d, segs[-1])
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:-9])    # mid-record, newline gone
    scan = scan_journal(d)
    assert scan.torn_tail and scan.records_dropped == 1
    assert scan.records == total - 1


def test_scan_bitflipped_checksum_mid_segment(tmp_path):
    """A flipped byte ANYWHERE fails the crc and drops that record only
    — and mid-file damage is NOT the torn-tail signature."""
    d = str(tmp_path / "j")
    total = _write_clean(d)
    segs = sorted(f for f in os.listdir(d) if f.startswith("journal-"))
    path = os.path.join(d, segs[0])     # first segment: nowhere near the tail
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0x40
    open(path, "wb").write(bytes(raw))
    scan = scan_journal(d)
    assert scan.records_dropped >= 1 and not scan.torn_tail
    assert scan.records + scan.records_dropped == total


def test_scan_empty_segment_and_gap(tmp_path):
    """An empty segment contributes nothing; a deleted segment number is
    surfaced in ``segment_gaps`` and costs only its own records."""
    d = str(tmp_path / "j")
    total = _write_clean(d)
    segs = sorted(f for f in os.listdir(d) if f.startswith("journal-"))
    assert len(segs) >= 3
    open(os.path.join(d, _segment_name(99)), "wb").close()  # empty segment
    victim = os.path.join(d, segs[1])
    lost = open(victim, "rb").read().count(b"\n")
    os.remove(victim)                                       # segment gap
    scan = scan_journal(d)
    assert segs[1] in scan.segment_gaps
    assert scan.records == total - lost
    assert not scan.torn_tail           # trailing empty segment isn't torn
    assert scan_journal(str(tmp_path / "nowhere")).records == 0


def test_scan_corruption_fuzz_seeded(tmp_path):
    """Seeded fuzz: random byte flips / truncations across the segment
    set never crash the scan, and every line is either parsed or counted
    dropped — the accounting always closes."""
    rng = random.Random(1234)
    for trial in range(8):
        d = str(tmp_path / f"j{trial}")
        total = _write_clean(d, n_requests=8, segment_bytes=250)
        scannable = 0
        for name in sorted(os.listdir(d)):
            path = os.path.join(d, name)
            raw = bytearray(open(path, "rb").read())
            op = rng.random()
            if raw and op < 0.4:               # flip a byte (may merge/
                raw[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
            elif raw and op < 0.7:             # split lines) / truncate
                del raw[rng.randrange(len(raw)):]
            open(path, "wb").write(bytes(raw))
            lines = bytes(raw).split(b"\n")    # scan's own line model
            if lines and lines[-1] == b"":
                lines.pop()
            scannable += len(lines)
        scan = scan_journal(d)                 # must not raise
        # the accounting closes: every scannable line is parsed or
        # counted dropped, and damage can only ever LOSE records
        assert scan.records + scan.records_dropped == scannable
        assert scan.records <= total
        # whatever survived is structurally sound: replay-able metas only
        for state in scan.incomplete():
            assert isinstance(state["meta"]["prompt"], list)
            assert isinstance(state["meta"]["max_new"], int)


def test_orphan_delivered_without_admitted(tmp_path):
    """delivered/retired whose admitted record was lost are counted
    orphans, never replayed (there is nothing to replay)."""
    d = str(tmp_path / "j")
    j = RequestJournal(d, fsync_policy="never")
    j.delivered(7, 3)
    j.retired(7, "done", None)
    j.close()
    scan = scan_journal(d)
    assert scan.orphan_records == 2 and scan.requests == {}
    assert scan.incomplete() == []


# ----------------------------------------------------------------------
# chaos: the journal-write site


def test_chaos_torn_write_drops_exactly_that_record(tmp_path):
    """``journal-write`` torn: a prefix lands with no newline, the
    segment is closed, survivor appends land cleanly after it — the scan
    loses exactly the torn record."""
    d = str(tmp_path / "j")
    chaos = FaultInjector(FaultPlan(seed=0, faults=(
        FaultSpec(site="journal-write", kind="torn", at=(2,)),)))
    j = RequestJournal(d, fsync_policy="never", chaos=chaos)
    j.admitted(_fake_dr(0))            # event 0
    j.delivered(0, 1)                  # event 1
    j.delivered(0, 2)                  # event 2: TORN
    j.delivered(0, 3)                  # survivor append, fresh segment
    j.retired(0, "done", None)
    j.close()
    assert j.stats()["chaos_torn"] == 1
    scan = scan_journal(d)
    assert scan.records_dropped == 1
    assert scan.requests[0]["delivered"] == 3   # later high-water survived
    assert scan.requests[0]["retired"] == "done"


def test_chaos_corrupt_write_caught_by_checksum(tmp_path):
    """``journal-write`` corrupt: full-length line, one flipped payload
    byte — the crc catches it and the scan drops exactly it."""
    d = str(tmp_path / "j")
    chaos = FaultInjector(FaultPlan(seed=0, faults=(
        FaultSpec(site="journal-write", kind="corrupt", at=(1,)),)))
    j = RequestJournal(d, fsync_policy="never", chaos=chaos)
    j.admitted(_fake_dr(0))
    j.delivered(0, 1)                  # CORRUPT
    j.delivered(0, 2)
    j.close()
    assert j.stats()["chaos_corrupt"] == 1
    scan = scan_journal(d)
    assert scan.records_dropped == 1 and not scan.torn_tail
    assert scan.requests[0]["delivered"] == 2


def test_chaos_io_fault_fails_the_submit(tmp_path, model_and_params):
    """An ``io``-kind journal fault at admission propagates out of
    ``submit()``: the caller is never acknowledged, nothing is counted
    submitted, and the tier keeps serving afterwards."""
    model, params = model_and_params
    d = str(tmp_path / "j")
    chaos = FaultInjector(FaultPlan(seed=0, faults=(
        FaultSpec(site="journal-write", kind="io", at=(0,)),)))
    j = RequestJournal(d, fsync_policy="never", chaos=chaos)
    router = Router(_factory(model, params), 1)
    daemon = ServingDaemon(router, journal=j)    # never started: queue only
    with pytest.raises(JournalWriteError):
        daemon.submit([1, 2, 3], 4)
    cons = daemon.conservation()
    assert cons["submitted"] == 0
    assert daemon.counters["journal_errors"] == 1
    dr = daemon.submit([1, 2, 3], 4)             # next submit lands
    assert daemon.conservation()["submitted"] == 1
    daemon.close()
    scan = scan_journal(d)
    assert scan.requests[dr.id]["retired"] == "cancelled"


# ----------------------------------------------------------------------
# daemon wiring + whole-process recovery


def test_daemon_journal_clean_run_leaves_no_incomplete(tmp_path,
                                                       model_and_params):
    """A journaled wave that completes and closes cleanly leaves zero
    incomplete entries, and every delivered high-water equals the
    request's final token count."""
    model, params = model_and_params
    want = _reference(model, params)
    d = str(tmp_path / "j")
    j = RequestJournal(d, fsync_policy="interval")
    router = Router(_factory(model, params), 2)
    daemon = ServingDaemon(router, journal=j)
    with daemon:
        drs = [daemon.submit(p, 6) for p in PROMPTS]
        assert all(dr.wait(WAIT_S) for dr in drs)
        assert [dr.tokens for dr in drs] == want
        summ = daemon.summary()
        assert summ["journal"]["by_type"]["admitted"] == len(PROMPTS)
    scan = scan_journal(d)
    assert scan.incomplete() == []
    for dr in drs:
        state = scan.requests[dr.id]
        assert state["delivered"] == len(dr.tokens)
        assert state["retired"] == "done"


def test_recover_replays_everything_from_scratch(tmp_path,
                                                 model_and_params):
    """SIGKILL-before-any-work: admitted records only.  ``recover()``
    re-submits every request into a fresh tier and the replayed streams
    are token-identical to the uncrashed reference (greedy AND seeded)."""
    model, params = model_and_params
    sp = SamplingParams(temperature=0.8, top_k=8, seed=7)
    want_greedy = _reference(model, params)
    want_seeded = _reference(model, params, sampling=sp)

    d = str(tmp_path / "j")
    j = RequestJournal(d)
    router = Router(_factory(model, params), 1)
    crashed = ServingDaemon(router, journal=j)   # never started
    for p in PROMPTS:
        crashed.submit(p, 6, idempotency_key=f"key-{len(p)}")
    for p in PROMPTS:
        crashed.submit(p, 6, sampling=sp)
    # the "crash": no drain, no close — the process is simply gone
    j.sync()

    rec = recover(d, lambda: ServingDaemon(
        Router(_factory(model, params), 2),
        journal=RequestJournal(d)))
    try:
        assert rec.scan.report()["incomplete"] == 2 * len(PROMPTS)
        assert len(rec.requests) == 2 * len(PROMPTS)
        assert rec.wait(WAIT_S)
        got = [r.dr.tokens for r in rec.requests]
        assert got[:len(PROMPTS)] == want_greedy
        assert got[len(PROMPTS):] == want_seeded
        assert all(r.dr.status == "done" for r in rec.requests)
        # the client's retry keys re-bound to the replayed executions
        assert set(rec.bindings) == {f"key-{len(p)}" for p in PROMPTS}
        assert rec.report()["replayed"] == 2 * len(PROMPTS)
    finally:
        rec.daemon.close()
    # recovery composes: fresh ids never collide with crashed ids, the
    # crashed entries are closed as "replayed", the replays retired —
    # a second recovery over this directory would find nothing to do
    scan = scan_journal(d)
    crashed_ids = {r.orig_id for r in rec.requests}
    replay_ids = {r.dr.id for r in rec.requests}
    assert crashed_ids.isdisjoint(replay_ids)
    assert all(scan.requests[i]["retired"] == "replayed"
               for i in crashed_ids)
    assert all(scan.requests[i]["retired"] == "done" for i in replay_ids)
    assert scan.report()["incomplete"] == 0


def test_recover_resumes_past_delivered_high_water(tmp_path,
                                                   model_and_params):
    """The exactly-once core: a delivered high-water of k makes the
    replay re-emit ONLY tokens [k:], and the stitched transcript
    (delivered prefix + replayed suffix) is digest-identical to the
    uncrashed stream — no gaps, no duplicates."""
    model, params = model_and_params
    sp = SamplingParams(temperature=0.9, top_p=0.9, seed=21)
    want = _reference(model, params, prompts=[PROMPTS[0]], max_new=6,
                      sampling=sp)[0]
    assert len(want) == 6

    d = str(tmp_path / "j")
    j = RequestJournal(d)
    dr0 = _fake_dr(0, prompt=PROMPTS[0], max_new=6, sampling=sp,
                   idempotency_key="resume-me")
    j.admitted(dr0)
    j.delivered(0, 2)      # client held tokens [0, 2) at the crash
    j.delivered(0, 4)      # ...then [0, 4): high-water is the MAX
    j.close()

    rec = recover(d, lambda: ServingDaemon(
        Router(_factory(model, params), 1),
        journal=RequestJournal(d)))
    try:
        assert rec.wait(WAIT_S)
        (r,) = rec.requests
        assert r.orig_id == 0 and r.resume_from == 4
        assert r.dr.resume_from == 4
        # ONLY the suffix was re-emitted...
        assert r.dr.tokens == want[4:]
        assert r.dr.total_tokens == len(want)
        # ...and prefix + suffix stitch into the exact uncrashed stream
        stitched = want[:4] + list(r.dr.tokens)
        assert transcript_digest(stitched) == transcript_digest(want)
        assert rec.bindings["resume-me"] is r.dr
    finally:
        rec.daemon.close()


def test_recover_lapsed_deadline_retires_cancelled(tmp_path,
                                                   model_and_params):
    """A request whose deadline lapsed while the process was dead is
    re-admitted already overdue and retires ``cancelled`` through the
    normal path — counted and journaled, never silently dropped."""
    model, params = model_and_params
    d = str(tmp_path / "j")
    j = RequestJournal(d)
    dr0 = _fake_dr(0, prompt=[1, 2, 3], max_new=6, deadline_s=0.5)
    meta_patch = dict(wall_t=1.0)      # admitted "long ago" in wall time
    # re-encode the admitted record with an ancient wall_t
    j.append({
        "t": "admitted", "id": 0, "prompt": [1, 2, 3], "max_new": 6,
        "deadline_s": 0.5, "priority": 0, "ttft_slo_s": None,
        "tpot_slo_s": None, "sampling": None, "key": None,
        "fp": dr0.fingerprint, "resume_from": 0, **meta_patch,
    })
    j.close()

    rec = recover(d, lambda: ServingDaemon(
        Router(_factory(model, params), 1),
        journal=RequestJournal(d)))
    try:
        assert rec.wait(WAIT_S)
        (r,) = rec.requests
        assert r.dr.status == "cancelled"
    finally:
        rec.daemon.close()
    cons = rec.daemon.conservation()
    assert cons["conserved"] and cons["cancelled"] >= 1
    # journal closure: the replay got its terminal record
    scan = scan_journal(d)
    assert scan.requests[r.dr.id]["retired"] == "cancelled"


def test_encode_decode_property(tmp_path):
    """Every encoded line is 8 hex chars + space + compact JSON +
    newline, and decodes back to the record."""
    from distributed_tensorflow_ibm_mnist_tpu.serving.journal import _decode
    rec = {"t": "delivered", "id": 3, "n": 11}
    line = _encode(rec)
    assert line.endswith(b"\n") and line[8:9] == b" "
    assert _decode(line[:-1]) == rec
    assert _decode(b"") is None
    assert _decode(b"deadbeef {not json}") is None
    flipped = bytearray(line[:-1])
    flipped[12] ^= 0x02
    assert _decode(bytes(flipped)) is None


def test_torn_retirement_record_reopens_and_replays_the_suffix(
        tmp_path, model_and_params):
    """The crash lands mid-append of the LAST record, a retirement: the
    scan flags the torn tail and drops exactly that record, which reopens
    its request; recovery replays it to ``done`` and re-emits only what
    the journal does not show as delivered."""
    model, params = model_and_params
    d = str(tmp_path / "j")
    daemon = ServingDaemon(Router(_factory(model, params), 1),
                           journal=RequestJournal(d)).start()
    drs = [daemon.submit(p, 6) for p in PROMPTS[:2]]
    assert all(dr.wait(WAIT_S) for dr in drs)
    want = {dr.id: list(dr.tokens) for dr in drs}
    daemon.drain(timeout=30.0)
    daemon.close()
    last = os.path.join(d, sorted(
        f for f in os.listdir(d) if f.endswith(".jsonl"))[-1])
    with open(last, "ab") as fh:
        fh.truncate(os.path.getsize(last) - 9)
    scan = scan_journal(d)
    assert scan.torn_tail and scan.records_dropped == 1
    rec = recover(d, lambda: ServingDaemon(
        Router(_factory(model, params), 1), journal=RequestJournal(d)))
    try:
        assert rec.wait(WAIT_S) and len(rec.requests) == 1
        (r,) = rec.requests
        assert r.dr.status == "done"
        assert list(r.dr.tokens) == want[r.orig_id][r.resume_from:]
        assert rec.daemon.drain(timeout=30.0)
    finally:
        rec.daemon.close()


def test_recover_keeps_each_traceparent_and_joins_both_generations(
        tmp_path, model_and_params):
    """The journal round-trips a request's trace identity bit for bit, and
    the merged export of the tracer that died and the tracer that replayed
    shows ONE connected tree a trace across both process generations."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
        TraceContext,
        Tracer,
        merge_traces,
        trace_forest,
        validate_trace,
    )

    model, params = model_and_params

    def tier(tracer, journal):
        return ServingDaemon(
            Router(_factory(model, params, tracer=tracer), 1, tracer=tracer),
            journal=journal)

    d = str(tmp_path / "j")
    pre_tr, post_tr = Tracer(), Tracer()
    j = RequestJournal(d)
    crashed = tier(pre_tr, j)                    # never started
    wanted = []
    for i, p in enumerate(PROMPTS):
        ctx = TraceContext.mint()
        crashed.submit(p, 4, trace_ctx=ctx, idempotency_key=f"rk-{i}")
        wanted.append(ctx.to_traceparent())
    j.sync()                                     # ... and the process is gone

    rec = recover(d, lambda: tier(post_tr, RequestJournal(d)))
    try:
        assert rec.wait(WAIT_S)
        assert sorted(r.dr.trace_ctx.to_traceparent()
                      for r in rec.requests) == sorted(wanted)
        assert rec.daemon.drain(timeout=30.0)
    finally:
        rec.daemon.close()
    assert post_tr.open_spans == 0
    post = str(tmp_path / "post.json")
    post_tr.export_trace(post)
    assert validate_trace(post) == []
    # the tracer that died holds daemon_request spans nobody closed, so
    # the merged document is for the forest, not for validate_trace
    doc = merge_traces([pre_tr, post_tr], str(tmp_path / "merged.json"),
                       names=["gen0", "gen1"])
    forest = trace_forest(doc)
    for tp in wanted:
        g = forest[TraceContext.parse_traceparent(tp).trace_id]
        assert g["connected"] and "daemon_request" in g["names"], g


# ----------------------------------------------------------------------
# a real SIGKILL: what the process wrote without being asked to sync


_CRASH_CHILD = """
import os, sys, time
import jax, jax.numpy as jnp
from distributed_tensorflow_ibm_mnist_tpu.models import get_model
from distributed_tensorflow_ibm_mnist_tpu.serving import (
    FIFOScheduler, InferenceEngine, RequestJournal, Router, SamplingParams,
    ServingDaemon)
from distributed_tensorflow_ibm_mnist_tpu.utils.metrics import MetricWriter
from distributed_tensorflow_ibm_mnist_tpu.utils.telemetry import Telemetry

work = sys.argv[1]
model = get_model("causal_lm", num_classes=16, dim=32, depth=1, heads=2,
                  dtype=jnp.float32)
params = model.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 8), jnp.int32))["params"]
daemon = ServingDaemon(
    Router(lambda tid: InferenceEngine(
        model, params, slots=2, max_len=64,
        scheduler=FIFOScheduler(max_len=64, buckets=(8,), max_queue=16),
        trace_tid=tid), 1),
    journal=RequestJournal(os.path.join(work, "journal"),
                           fsync_policy="always")).start()
tele = Telemetry(interval_s=0.05, fsync=True,
                 jsonl_path=os.path.join(work, "telemetry.jsonl"))
tele.register_source("daemon", daemon.summary)
mw = MetricWriter(os.path.join(work, "metrics.jsonl"), stdout=False,
                  fsync=True)
client = open(os.path.join(work, "client.log"), "a")

def received(dr, tok):          # the client's side of the stream
    client.write(f"{dr.idempotency_key} {int(tok)}\\n")
    client.flush()

prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
n = 0
while True:                     # the parent's SIGKILL is the only exit
    if daemon.conservation()["outstanding"] < 4:
        sp = (SamplingParams(temperature=0.7, top_k=5, seed=100 + n)
              if n % 2 else None)
        daemon.submit(prompts[n % 3], 40, sampling=sp, callback=received,
                      idempotency_key=f"k{n}")
        n += 1
    tele.maybe_sample()
    mw.write("serving", requests=n)
    time.sleep(0.002)
"""


def test_sigkill_mid_stream_recovers_exactly_once(tmp_path,
                                                  model_and_params):
    """A serving process (journal at ``always``, fsync'd telemetry and
    metric logs) is killed with ``kill -9`` while requests stream.  From
    nothing but what it left on disk: the kill landed mid-flight; every
    request the journal shows unretired replays to ``done``; a client's
    transcript (what it held at the kill, then the replay from the
    journal's high-water mark) has no gap, no divergent duplicate, and
    the digest of an uncrashed run of the same request, greedy and
    seeded-sampled; the keys rebind; the black box is readable."""
    model, params = model_and_params
    work = str(tmp_path)
    jdir = os.path.join(work, "journal")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD, work], cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo),
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    try:
        deadline = time.monotonic() + 240.0
        while True:             # ... until some retired and some stream
            assert proc.poll() is None, proc.stderr.read()[-2000:]
            assert time.monotonic() < deadline, "the child never served"
            time.sleep(0.02)
            try:
                states = list(scan_journal(jdir).requests.values())
            except OSError:
                continue
            if (any(v["retired"] == "done" for v in states)
                    and any(not v["retired"] and v["delivered"] > 0
                            for v in states)):
                break
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.kill()
        proc.wait(timeout=30.0)
        proc.stderr.close()

    held: dict[str, list[int]] = {}
    with open(os.path.join(work, "client.log")) as fh:
        for line in fh:
            key, _, tok = line.partition(" ")
            if tok.endswith("\n"):          # a torn last line is not held
                held.setdefault(key, []).append(int(tok))

    rec = recover(jdir, lambda: ServingDaemon(
        Router(_factory(model, params, max_len=64), 1),
        journal=RequestJournal(jdir)))
    try:
        assert len(rec.requests) >= 1            # the kill landed mid-flight
        assert rec.wait(WAIT_S)
        assert {r.dr.idempotency_key for r in rec.requests} == set(
            rec.bindings)
        replayed = {r.dr.idempotency_key: r for r in rec.requests}
        for key in sorted(set(held) | set(replayed), key=lambda k: int(k[1:])):
            n = int(key[1:])
            ref = rec.daemon.submit(
                PROMPTS[n % 3], 40, sampling=SamplingParams(
                    temperature=0.7, top_k=5, seed=100 + n) if n % 2 else None)
            assert ref.wait(WAIT_S)
            have = held.get(key, [])
            if key in replayed:
                r = replayed[key]
                assert r.dr.status == "done"
                # the journal never overstates what the client received
                assert r.resume_from <= len(have)
                for idx, tok in enumerate(r.dr.tokens, start=r.resume_from):
                    if idx < len(have):
                        assert have[idx] == tok      # a duplicate, the same
                    else:
                        assert idx == len(have)      # no gap
                        have.append(tok)
            assert transcript_digest(have) == transcript_digest(
                list(ref.tokens)), key
        assert rec.daemon.drain(timeout=30.0)
    finally:
        rec.daemon.close()
    assert scan_journal(jdir).report()["incomplete"] == 0
    for name in ("telemetry.jsonl", "metrics.jsonl"):
        with open(os.path.join(work, name)) as fh:
            lines = [ln for ln in fh if ln.endswith("\n")]
        assert lines and all(json.loads(ln) is not None for ln in lines[:-1])
