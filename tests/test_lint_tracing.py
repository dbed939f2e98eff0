"""The tracing-contract lint as a tier-1 test (ISSUE 19 satellite).

``scripts/lint_tracing.py`` enforces two mechanical invariants over the
serving package — every ``_tracer`` call is nil-guarded (zero-cost-off)
and no serving code reads ``time.time()`` (monotonic clock domain,
journal.py excepted) — and one over the whole package: a ``host_span``
name is a literal of docs/OBSERVABILITY.md's table, never built from a
request id, slot or count.  Running it from pytest makes a regression a RED
test, not a forgotten CI step; the unit cases below pin that the checker
itself still catches what it claims to catch.
"""

import importlib.util
import os
import sys

import pytest

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load():
    spec = importlib.util.spec_from_file_location(
        "lint_tracing", os.path.join(_SCRIPTS, "lint_tracing.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lint = _load()


# ----------------------------------------------------------------------
# the real gate: the serving package is clean


def test_serving_package_is_clean():
    violations = lint.check_serving()
    assert violations == [], "\n".join(violations)


# ----------------------------------------------------------------------
# the checker catches what it claims to catch


def test_flags_unguarded_tracer_call():
    src = ("class E:\n"
           "    def f(self):\n"
           "        self._tracer.begin('x')\n")
    out = lint.check_source(src, "mod.py")
    assert len(out) == 1 and "unguarded tracer call" in out[0]


def test_accepts_if_not_none_body():
    src = ("class E:\n"
           "    def f(self):\n"
           "        if self._tracer is not None:\n"
           "            self._tracer.begin('x')\n")
    assert lint.check_source(src, "mod.py") == []


def test_accepts_conjoined_guard():
    src = ("class E:\n"
           "    def f(self, req):\n"
           "        if self._tracer is not None and req.trace is not None:\n"
           "            self._tracer.instant('x')\n")
    assert lint.check_source(src, "mod.py") == []


def test_accepts_early_return_guard():
    src = ("class E:\n"
           "    def f(self, t):\n"
           "        if self._tracer is None or t is None:\n"
           "            return\n"
           "        self._tracer.end(t)\n")
    assert lint.check_source(src, "mod.py") == []


def test_rejects_wrong_branch():
    # the call sits in the `is None` BODY — exactly backwards
    src = ("class E:\n"
           "    def f(self):\n"
           "        if self._tracer is None:\n"
           "            self._tracer.begin('x')\n")
    out = lint.check_source(src, "mod.py")
    assert len(out) == 1


def test_accepts_else_branch_of_is_none():
    src = ("class E:\n"
           "    def f(self):\n"
           "        if self._tracer is None:\n"
           "            pass\n"
           "        else:\n"
           "            self._tracer.begin('x')\n")
    assert lint.check_source(src, "mod.py") == []


def test_early_return_must_precede_the_call():
    src = ("class E:\n"
           "    def f(self):\n"
           "        self._tracer.begin('x')\n"
           "        if self._tracer is None:\n"
           "            return\n")
    out = lint.check_source(src, "mod.py")
    assert len(out) == 1


def test_accepts_conditional_expression_and_derived_guard():
    # the engine's prefill-span idiom: assign under an IfExp guard, then
    # close under `if span is not None:`
    src = ("class E:\n"
           "    def f(self, req):\n"
           "        span = (self._tracer.begin('prefill')\n"
           "                if self._tracer is not None"
           " and req.trace is not None else None)\n"
           "        try:\n"
           "            pass\n"
           "        finally:\n"
           "            if span is not None:\n"
           "                self._tracer.end(span)\n")
    assert lint.check_source(src, "mod.py") == []


def test_flags_wall_clock_in_serving():
    src = ("import time\n"
           "def f():\n"
           "    return time.time()\n")
    out = lint.check_source(src, "engine.py")
    assert len(out) == 1 and "time.time()" in out[0]


def test_wall_clock_allowlisted_for_journal():
    src = ("import time\n"
           "def f():\n"
           "    return time.time()\n")
    assert lint.check_source(src, "journal.py") == []


def test_monotonic_is_fine():
    src = ("import time\n"
           "def f():\n"
           "    return time.monotonic()\n")
    assert lint.check_source(src, "engine.py") == []


# ----------------------------------------------------------------------
# the host-span name contract (ISSUE 25): names are documented literals


def test_package_host_spans_are_documented_literals():
    violations = lint.check_package_host_spans()
    assert violations == [], "\n".join(violations)
    names = lint.documented_span_names()
    assert {"engine.step", "engine.emit", "site:<label>"} <= names


@pytest.mark.parametrize("call,finds", [
    ("host_span('engine.step', occupied=n)", None),
    ("host_span('site:' + label)", None),
    ("host_span(f'site:{label}')", None),
    ("tracing.host_span('engine.emit')", None),
    ("host_span('engine.undocumented')", "not in docs"),
    ("host_span(f'engine.step[{req.id}]')", "built from"),
    ("host_span('site:' + str(slot))", "built from slot"),
    ("host_span(f'engine.emit[{n_tokens}]')", "built from n_tokens"),
    ("host_span(name)", "not in docs"),
    ("jax.profiler.TraceAnnotation('engine.step')", "TraceAnnotation outside"),
])
def test_host_span_name_contract(call, finds):
    documented = {"engine.step", "engine.emit", "site:<label>"}
    src = f"def f(req, slot, label, n, n_tokens, name):\n    with {call}:\n        pass\n"
    out = lint.check_host_spans(src, "pkg/serving/engine.py", documented)
    if finds is None:
        assert out == []
    else:
        assert out and any(finds in v for v in out), out


def test_trace_annotation_is_allowed_in_its_one_home():
    src = "from jax.profiler import TraceAnnotation\n"
    assert lint.check_host_spans(src, "pkg/utils/tracing.py", set()) == []
    assert len(lint.check_host_spans(src, "pkg/utils/other.py", set())) == 1


def test_cli_exit_status():
    import subprocess
    r = subprocess.run([sys.executable,
                        os.path.join(_SCRIPTS, "lint_tracing.py")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 violation(s)" in r.stdout


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
