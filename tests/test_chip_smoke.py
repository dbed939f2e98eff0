"""chip_smoke.py's contract, as far as a machine without a chip can show it.

The smoke itself only passes on a TPU (the driver runs it there after every
PR).  Here: without a chip it fails and prints no result; its parent process
never imports jax (one process per chip — the legs are children); and the
``--cpu-dry-run`` flag drives every leg at tiny sizes without ever printing
the pass line.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

# main() in-process, then report the parent's own module table
PARENT = (
    "import json, sys, chip_smoke\n"
    "rc = chip_smoke.main(sys.argv[1:])\n"
    "print(json.dumps({'parent_rc': rc, 'parent_imported_jax': 'jax' in sys.modules}))\n"
)


def _run(*argv, timeout=560):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device: the multichip legs stay out
    return subprocess.run(
        [sys.executable, "-c", PARENT, *argv], cwd=str(REPO), env=env,
        capture_output=True, text=True, timeout=timeout)


def _json_lines(text: str) -> list[dict]:
    out = []
    for line in text.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict):
            out.append(rec)
    return out


def test_no_tpu_fails_without_a_result_and_parent_stays_off_jax():
    proc = _run()
    recs = _json_lines(proc.stdout)
    # the only stdout line is this test's own parent report: the smoke
    # printed no record and no pass line
    assert [sorted(r) for r in recs] == [["parent_imported_jax", "parent_rc"]]
    assert recs[0]["parent_rc"] != 0
    assert recs[0]["parent_imported_jax"] is False
    assert "no TPU found" in proc.stderr
    # and as a script: a nonzero exit code
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                            env=env, capture_output=True, text=True, timeout=300)
    assert script.returncode != 0 and script.stdout.strip() == ""


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo: the
    legs cannot import the package — exit nonzero, no result."""
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-dry-run"], cwd=str(tmp_path),
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.slow
def test_cpu_dry_run_passes_every_leg_but_never_prints_the_pass_line():
    """~2 min of child processes, so outside the tier-1 time box; the
    driver's own chip run of the smoke is the per-PR gate."""
    proc = _run("--cpu-dry-run", timeout=900)
    recs = _json_lines(proc.stdout)
    report = recs[-1]
    assert report == {"parent_rc": 0, "parent_imported_jax": False}, proc.stderr[-3000:]
    legs = {r["leg"]: r for r in recs if "leg" in r}
    assert set(legs) == {"lenet", "lenet_second_process", "lm", "kernels", "engine"}
    for rec in legs.values():
        assert rec["ok"] and rec["dry_run"] is True
        assert rec["device"]["platform"] == "cpu"
        assert rec["pallas_interpreted"] is True  # and said so
    assert all(c["post_prewarm_programs"] == 0
               for c in legs["engine"]["layouts"].values())
    assert legs["engine"]["frontdoor"] == {"unary": "ok", "sse": "ok",
                                           "healthz": "ok"}
    summary = recs[-2]
    assert summary["dry_run"] is True and summary["legs_ok"] is True
    assert summary["multichip"] == "not run: 1 device(s)"
    # nothing on stdout can be mistaken for a pass
    assert not any(r.get("ok") is True and "leg" not in r for r in recs)
