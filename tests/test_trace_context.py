"""Distributed trace-context unit suite (ISSUE 19 satellite).

Pins the W3C ``traceparent`` surface (:class:`TraceContext`), the
head+tail sampler, span links/annotation, the multi-tracer merge, the
forest connectivity checker, and the exemplar-bearing OpenMetrics
exposition — the building blocks the serving tier's end-to-end tracing
(tests/test_frontend.py, tests/test_disagg.py, tests/test_journal.py) is
assembled from.
"""

import io
import json

import pytest

from distributed_tensorflow_ibm_mnist_tpu.serving.frontend import (
    _sanitize_request_id,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.telemetry import (
    MetricsRegistry,
)
from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import (
    TraceContext,
    Tracer,
    TraceSampler,
    merge_traces,
    trace_forest,
    validate_trace,
)

TID = "0af7651916cd43dd8448eb211c80319c"
SID = "b7ad6b7169203331"


# ----------------------------------------------------------------------
# TraceContext: mint / parse / round-trip


def test_mint_well_formed():
    ctx = TraceContext.mint()
    assert len(ctx.trace_id) == 32 and int(ctx.trace_id, 16) != 0
    assert len(ctx.span_id) == 16 and int(ctx.span_id, 16) != 0
    assert ctx.trace_id == ctx.trace_id.lower()
    assert ctx.sampled is True


def test_mint_unique():
    seen = {TraceContext.mint().trace_id for _ in range(64)}
    assert len(seen) == 64


def test_traceparent_round_trip():
    for sampled in (True, False):
        ctx = TraceContext(TID, SID, sampled=sampled)
        back = TraceContext.parse_traceparent(ctx.to_traceparent())
        assert back == ctx
        assert back.sampled is sampled


def test_to_traceparent_format():
    assert (TraceContext(TID, SID, sampled=True).to_traceparent()
            == f"00-{TID}-{SID}-01")
    assert (TraceContext(TID, SID, sampled=False).to_traceparent()
            == f"00-{TID}-{SID}-00")


def test_child_same_trace_fresh_span():
    ctx = TraceContext(TID, SID, sampled=False)
    kid = ctx.child()
    assert kid.trace_id == TID
    assert kid.span_id != SID
    assert kid.sampled is False


def test_ctor_rejects_bad_ids():
    with pytest.raises(ValueError):
        TraceContext("0" * 32, SID)          # all-zero trace id
    with pytest.raises(ValueError):
        TraceContext(TID, "0" * 16)          # all-zero span id
    with pytest.raises(ValueError):
        TraceContext(TID[:-1], SID)          # short
    with pytest.raises(ValueError):
        TraceContext(TID.upper(), SID)       # uppercase


@pytest.mark.parametrize("header", [
    None,
    "",
    "garbage",
    f"00-{TID}-{SID}",                       # missing flags
    f"00-{'0' * 32}-{SID}-01",               # all-zero trace id
    f"00-{TID}-{'0' * 16}-01",               # all-zero span id
    f"00-{TID.upper()}-{SID}-01",            # uppercase hex
    f"00-{TID}-{SID}-0g",                    # non-hex flags
    f"ff-{TID}-{SID}-01",                    # forbidden version
    f"00-{TID}-{SID}-01-extra",              # v00 must have exactly 4
    f"0-{TID}-{SID}-01",                     # short version
    f"00-{TID[:-2]}-{SID}-01",               # short trace id
])
def test_parse_rejects(header):
    assert TraceContext.parse_traceparent(header) is None


def test_parse_future_version_tolerant():
    # a future version may append fields — first four still parse
    ctx = TraceContext.parse_traceparent(f"cc-{TID}-{SID}-01-what-ever")
    assert ctx is not None and ctx.trace_id == TID and ctx.sampled


def test_parse_honors_flags():
    assert TraceContext.parse_traceparent(f"00-{TID}-{SID}-00").sampled is False
    assert TraceContext.parse_traceparent(f"00-{TID}-{SID}-01").sampled is True


# ----------------------------------------------------------------------
# TraceSampler: head determinism + tail always-keep


def test_head_extremes_and_determinism():
    assert TraceSampler(rate=1.0).head(TID) is True
    assert TraceSampler(rate=0.0).head(TID) is False
    s = TraceSampler(rate=0.5)
    assert s.head(TID) == s.head(TID)
    # deterministic on the id prefix: low prefix in, high prefix out
    assert s.head("0" * 7 + "1" + "0" * 24) is True
    assert s.head("f" * 32) is False


def test_bad_rate_rejected():
    with pytest.raises(ValueError):
        TraceSampler(rate=1.5)
    with pytest.raises(ValueError):
        TraceSampler(rate=-0.1)


def test_tail_keep_rules():
    s = TraceSampler(rate=0.0)
    assert s.keep([{"name": "x", "args": {"status": "failed"}}])
    assert s.keep([{"name": "x", "args": {"status": "cancelled"}}])
    assert s.keep([{"name": "shed", "args": {}}])
    assert s.keep([{"name": "x", "args": {"slo_miss": True}}])
    assert s.keep([{"name": "x", "args": {"error": "boom"}}])
    assert s.keep([{"name": "x", "args": {"sampled": True}}])  # head verdict
    assert not s.keep([{"name": "x", "args": {"status": "done"}}])


# ----------------------------------------------------------------------
# annotate + links + sampled export


def _one_trace(tr, trace_id, status="done", sampled=True):
    root = tr.begin("request", trace=trace_id, sampled=sampled)
    child = tr.begin("work", parent=root)
    tr.end(child)
    tr.end(root, status=status)
    return root


def test_annotate_reparent_links_args():
    tr = Tracer()
    a = tr.begin("attempt0")
    b = tr.begin("attempt1")
    assert tr.annotate(b, parent=a, links=[a], replica=3) is True
    tr.end(b)
    tr.end(a)
    evs = {e["name"]: e for e in tr.events()}
    assert evs["attempt1"]["parent"] == a
    assert evs["attempt1"]["args"]["links"] == [a]
    assert evs["attempt1"]["args"]["replica"] == 3


def test_annotate_closed_span_is_noop():
    tr = Tracer()
    a = tr.begin("x")
    tr.end(a)
    assert tr.annotate(a, status="late") is False


def test_links_survive_export_and_validate(tmp_path):
    tr = Tracer()
    a = tr.begin("attempt0", trace=TID, sampled=True)
    tr.end(a, status="failed")
    b = tr.begin("attempt1", trace=TID, sampled=True)
    tr.annotate(b, links=[a])
    tr.end(b, status="done")
    path = str(tmp_path / "t.json")
    tr.export_trace(path)
    assert validate_trace(path) == []
    doc = json.load(open(path))
    linked = [e for e in doc["traceEvents"]
              if e.get("args", {}).get("links")]
    assert len(linked) == 1


def test_sampler_filters_whole_trace_groups(tmp_path):
    tr = Tracer()
    _one_trace(tr, "aa" * 16, sampled=False)              # dropped
    _one_trace(tr, "bb" * 16, sampled=True)               # head-kept
    _one_trace(tr, "cc" * 16, status="failed", sampled=False)  # tail-kept
    path = str(tmp_path / "s.json")
    tr.export_trace(path, sampler=TraceSampler(rate=0.0))
    assert validate_trace(path) == []
    traces = {e.get("args", {}).get("trace")
              for e in json.load(open(path))["traceEvents"]}
    assert "aa" * 16 not in traces
    assert "bb" * 16 in traces and "cc" * 16 in traces


def test_trace_events_closure():
    tr = Tracer()
    root = tr.begin("request", trace=TID)
    child = tr.begin("work", parent=root)
    tr.instant("mark", parent=child)
    tr.end(child)
    tr.end(root)
    _one_trace(tr, "dd" * 16)   # unrelated
    evs = tr.trace_events(TID)
    assert {e["name"] for e in evs} == {"request", "work", "mark"}


# ----------------------------------------------------------------------
# merge + forest


def test_merge_connects_processes_and_forest_agrees(tmp_path):
    front, back = Tracer(), Tracer()
    f_root = front.begin("http_request", trace=TID, sampled=True,
                         span_ctx=SID)
    front.end(f_root, status="done")
    b_root = back.begin("daemon_request", trace=TID, parent_ctx=SID)
    b_child = back.begin("work", parent=b_root)
    back.end(b_child)
    back.end(b_root, status="done")
    path = str(tmp_path / "m.json")
    doc = merge_traces([front, back], path, names=["front", "back"])
    assert validate_trace(path) == []
    pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(pids) == 2
    forest = trace_forest(doc)
    g = forest[TID]
    assert g["connected"] is True
    assert g["spans"] == 3
    assert {"http_request", "daemon_request", "work"} <= set(g["names"])


def test_forest_flags_disconnected():
    tr = Tracer()
    a = tr.begin("island_a", trace=TID)
    tr.end(a)
    b = tr.begin("island_b", trace=TID)   # same trace id, no edge
    tr.end(b)
    g = trace_forest(tr.to_doc())[TID]
    assert g["connected"] is False
    assert len(g["roots"]) == 2


def test_merge_into_buffer():
    tr = Tracer()
    _one_trace(tr, TID)
    buf = io.StringIO()
    merge_traces([tr], buf)
    assert json.loads(buf.getvalue())["traceEvents"]


# ----------------------------------------------------------------------
# exemplars / OpenMetrics


def test_openmetrics_exemplars_and_shape():
    reg = MetricsRegistry()
    reg.inc("requests", 3)
    reg.set_gauge("depth", 2.0)
    reg.observe("ttft_s", 0.01, exemplar=TID)
    reg.observe("ttft_s", 123456.0, exemplar="ee" * 16)  # overflow bucket
    text = reg.to_openmetrics()
    assert text.rstrip().endswith("# EOF")
    assert "dtm_requests_total 3" in text
    lines = [l for l in text.splitlines() if " # {" in l]
    assert any(f'trace_id="{TID}"' in l for l in lines)
    inf = [l for l in lines if 'le="+Inf"' in l]
    assert inf and 'trace_id="' + "ee" * 16 + '"' in inf[0]
    # classic exposition unchanged — no exemplar syntax leaks in
    assert " # {" not in reg.to_prometheus()


def test_exemplar_none_is_fine():
    reg = MetricsRegistry()
    reg.observe("x_s", 0.5)
    reg.observe("x_s", 0.5, exemplar=None)
    assert 'le="+Inf"' in reg.to_openmetrics()


# ----------------------------------------------------------------------
# front-door request-id sanitizer (satellite 2)


@pytest.mark.parametrize("raw,want", [
    ("abc-123", "abc-123"),
    ("A.b:c_d-9", "A.b:c_d-9"),
    ("x" * 64, "x" * 64),
    ("x" * 65, None),          # over the cap
    ("", None),
    (None, None),
    ("has space", None),
    ("new\r\nline: inject", None),
    ("héllo", None),
    (123, None),
])
def test_sanitize_request_id(raw, want):
    assert _sanitize_request_id(raw) == want
