#!/usr/bin/env python
"""Static tracing-contract lint (ISSUE 19 satellite; host spans ISSUE 25).

Two invariants keep the serving tier's tracing zero-cost-off and
clock-sane, a third keeps the spans on the profiler's clock readable, and
all are mechanical enough to enforce with ``ast`` instead of code review:

1. **Nil-guard contract.**  Every call through a ``_tracer`` attribute
   (``self._tracer.begin(...)``, ``engine._tracer.complete(...)``) must
   be guarded the way ``_chaos``/``_telemetry``/``_journal`` calls are:
   either lexically inside the body of an ``if <x>._tracer is not
   None:`` (or the else-branch of an ``is None`` test), or in a function
   that already bailed early through ``if <x>._tracer is None:
   return/raise/continue``.  An unguarded call is a crash on the
   default ``tracer=None`` configuration, which is promised to cost
   nothing (tests/test_tracing.py::test_tracerless_engine_has_no_tracer_state).

2. **Monotonic-clock contract.**  Serving code must not read
   ``time.time()``: span math runs on the tracer's ``time.monotonic``
   domain, and a wall-clock read silently produces garbage durations
   the moment NTP steps the clock.  ``serving/journal.py`` is the one
   allowlisted file — its two wall-clock reads are the *intentional*
   restart-surviving timestamps the journal format documents.

3. **Host-span name contract** (the whole package).  The name passed to
   ``host_span(...)`` is a literal listed in docs/OBSERVABILITY.md's
   event table, or a listed literal prefix plus a compile-site label
   (``"site:" + label``): a metric reads a span BY NAME, so a name built
   from a request id, a slot or a token count would be one name per
   request and match nothing — those go in the keyword arguments.  And
   ``TraceAnnotation`` is named in ``utils/tracing.py`` alone: every span
   the package records on the profiler's clock goes through ``host_span``.

Run as a script (``python scripts/lint_tracing.py``) for CI — exits
nonzero listing every violation — or import :func:`check_source` /
:func:`check_file` from tests (tests/test_lint_tracing.py wires this
into tier 1, so the contract regresses loudly, not silently).
"""

from __future__ import annotations

import ast
import os
import re
import sys

# files whose time.time() reads are intentionally wall-clock (the
# journal's restart-surviving timestamps) — everything else in serving/
# must stay on the tracer's monotonic domain
WALL_CLOCK_ALLOWLIST = ("journal.py",)


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:          # pragma: no cover - unparse is stdlib-solid
        return ""


def _is_tracer_call(node: ast.Call) -> bool:
    """``<anything>._tracer.<method>(...)`` — a call THROUGH the tracer."""
    f = node.func
    return (isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Attribute)
            and f.value.attr == "_tracer")


def _is_wall_clock_call(node: ast.Call) -> bool:
    """``time.time()`` exactly (not ``self.clock()``/``time.monotonic``)."""
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "time"
            and isinstance(f.value, ast.Name) and f.value.id == "time")


def _guard_exprs(test: ast.AST, op: type) -> list[str]:
    """The atomic comparison sources inside a boolean-joined if-test —
    splitting on ``op`` only: ``and`` for the positive guard (every
    conjunct must hold in the body) and ``or`` for the bail-out guard
    (any disjunct fires the early return / forces the else branch)."""
    if isinstance(test, ast.BoolOp) and isinstance(test.op, op):
        out: list[str] = []
        for v in test.values:
            out.extend(_guard_exprs(v, op))
        return out
    return [_unparse(test)]


def _tests_not_none(test: ast.AST) -> bool:
    return any(s.endswith("._tracer is not None") or s == "_tracer is not None"
               for s in _guard_exprs(test, ast.And))


def _tests_is_none(test: ast.AST) -> bool:
    return any(s.endswith("._tracer is None") or s == "_tracer is None"
               for s in _guard_exprs(test, ast.Or))


def _bails(stmts: list[ast.stmt]) -> bool:
    """Does this branch end control flow (early-return guard shape)?"""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue))


class _Walker(ast.NodeVisitor):
    """Tracks, for every node, the ancestor (node, field) path — enough
    to decide which BRANCH of an ``if`` a tracer call lives in."""

    def __init__(self, filename: str):
        self.filename = filename
        self.path: list[tuple[ast.AST, str]] = []
        self.violations: list[str] = []

    # -- guard resolution ------------------------------------------------

    def _guarded(self, call: ast.Call) -> bool:
        func_node = None
        derived: list[str] = []   # `if <name> is not None:` guard names
        for node, field in reversed(self.path):
            if isinstance(node, (ast.If, ast.IfExp)):
                if field == "body" and _tests_not_none(node.test):
                    return True
                if field == "orelse" and _tests_is_none(node.test):
                    return True
                if field == "body":
                    src = _unparse(node.test)
                    if src.endswith(" is not None"):
                        derived.append(src[: -len(" is not None")])
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and func_node is None):
                func_node = node
        if func_node is None:
            return False
        for stmt in ast.walk(func_node):
            # early-return form: ``if ..._tracer is None: return`` earlier
            # in the same function covers everything after it
            if (isinstance(stmt, ast.If) and _tests_is_none(stmt.test)
                    and _bails(stmt.body)
                    and stmt.lineno < call.lineno):
                return True
            # derived-guard form: the call sits under ``if span is not
            # None:`` and `span` was itself assigned tracer-conditionally
            # (``span = ... if self._tracer is not None ... else None``)
            if (isinstance(stmt, ast.Assign) and derived
                    and stmt.lineno < call.lineno
                    and "_tracer is not None" in _unparse(stmt.value)):
                for tgt in stmt.targets:
                    if _unparse(tgt) in derived:
                        return True
        return False

    # -- traversal -------------------------------------------------------

    def generic_visit(self, node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            if _is_tracer_call(node):
                if not self._guarded(node):
                    self.violations.append(
                        f"{self.filename}:{node.lineno}: unguarded tracer "
                        f"call `{_unparse(node.func)}(...)` — wrap in "
                        f"`if ..._tracer is not None:`")
            if (_is_wall_clock_call(node)
                    and os.path.basename(self.filename)
                    not in WALL_CLOCK_ALLOWLIST):
                self.violations.append(
                    f"{self.filename}:{node.lineno}: time.time() in serving "
                    f"code — use the tracer/engine monotonic clock "
                    f"(wall-clock is journal.py's exception, by design)")
        for field, value in ast.iter_fields(node):
            if isinstance(value, list):
                for item in value:
                    if isinstance(item, ast.AST):
                        self.path.append((node, field))
                        self.generic_visit(item)
                        self.path.pop()
            elif isinstance(value, ast.AST):
                self.path.append((node, field))
                self.generic_visit(value)
                self.path.pop()


# identifiers that may not feed a host_span NAME: per-request or
# per-iteration values, one distinct name each
_HIGH_CARDINALITY = ("req", "rid", "slot", "tok", "count", "len")


def _high_cardinality(word: str) -> bool:
    return (word == "id" or word.endswith("_id")
            or any(h in word for h in _HIGH_CARDINALITY))


def _name_parts(node: ast.AST) -> tuple[str | None, list[ast.AST]]:
    """``(literal head, dynamic parts)`` of a host_span name expression:
    a constant, ``"head" + expr``, or an f-string with a literal head."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, []
    if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add)
            and isinstance(node.left, ast.Constant)
            and isinstance(node.left.value, str)):
        return node.left.value, [node.right]
    if (isinstance(node, ast.JoinedStr) and node.values
            and isinstance(node.values[0], ast.Constant)):
        return node.values[0].value, list(node.values[1:])
    return None, [node]


def documented_span_names(doc_path: str | None = None) -> set[str]:
    """Every back-quoted token of docs/OBSERVABILITY.md's table rows."""
    doc_path = doc_path or os.path.join(
        os.path.dirname(package_dir()), "docs", "OBSERVABILITY.md")
    with open(doc_path, encoding="utf-8") as f:
        rows = [ln for ln in f if ln.startswith("|")]
    return set(re.findall(r"`([^`]+)`", "".join(rows)))


def check_host_spans(src: str, filename: str,
                     documented: set[str]) -> list[str]:
    """The host-span name contract over one source string."""
    out: list[str] = []
    is_home = filename.replace(os.sep, "/").endswith("utils/tracing.py")
    for node in ast.walk(ast.parse(src, filename=filename)):
        if (not is_home and isinstance(node, (ast.Name, ast.Attribute, ast.alias))
                and "TraceAnnotation" in (
                    getattr(node, "id", None), getattr(node, "attr", None),
                    getattr(node, "name", None))):
            out.append(
                f"{filename}:{node.lineno}: TraceAnnotation outside "
                f"utils/tracing.py — record the span through host_span()")
        if not (isinstance(node, ast.Call) and node.args and (
                getattr(node.func, "id", None) == "host_span"
                or getattr(node.func, "attr", None) == "host_span")):
            continue
        head, dynamic = _name_parts(node.args[0])
        listed = head is not None and (
            head in documented if not dynamic
            else any(d.startswith(head) and d != head for d in documented))
        if not listed:
            out.append(
                f"{filename}:{node.lineno}: host_span name "
                f"`{_unparse(node.args[0])}` is not in "
                f"docs/OBSERVABILITY.md's event table")
        for part in dynamic:
            words = [n.id for n in ast.walk(part) if isinstance(n, ast.Name)]
            words += [n.attr for n in ast.walk(part)
                      if isinstance(n, ast.Attribute)]
            bad = sorted({w for w in words if _high_cardinality(w.lower())})
            if bad:
                out.append(
                    f"{filename}:{node.lineno}: host_span name built from "
                    f"{', '.join(bad)} — a request id, slot or count goes "
                    f"in the keyword arguments, never in the name")
    return out


def check_source(src: str, filename: str = "<string>") -> list[str]:
    """Lint one source string; returns violation messages (empty = clean)."""
    w = _Walker(filename)
    w.generic_visit(ast.parse(src, filename=filename))
    return w.violations


def check_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        return check_source(f.read(), path)


def package_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "distributed_tensorflow_ibm_mnist_tpu")


def serving_dir() -> str:
    return os.path.join(package_dir(), "serving")


def check_serving() -> list[str]:
    """Lint every module in the serving package."""
    out: list[str] = []
    for name in sorted(os.listdir(serving_dir())):
        if name.endswith(".py"):
            out.extend(check_file(os.path.join(serving_dir(), name)))
    return out


def check_package_host_spans() -> list[str]:
    """The host-span name contract over every module of the package."""
    documented = documented_span_names()
    out: list[str] = []
    for root, _dirs, files in sorted(os.walk(package_dir())):
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, encoding="utf-8") as f:
                    out.extend(check_host_spans(f.read(), path, documented))
    return out


def main() -> int:
    violations = check_serving() + check_package_host_spans()
    for v in violations:
        print(v)
    n = len([f for f in os.listdir(serving_dir()) if f.endswith(".py")])
    print(f"lint_tracing: {n} files, {len(violations)} violation(s)")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
