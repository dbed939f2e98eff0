"""``sala-d12.longdoc-closed`` rehearsed end to end on the CPU at a tiny
preset (hidden 128, one period of the 1:3 pattern, ``dense_len`` 64, blocks
of 8, top-4, a window of 32, so that every request selects): the runner, the
check against the plain reference, and the new per-layer readers.  A
rehearsal proves paths, arguments and control flow; its numbers are never
measurements.  The readers that need a device trace are driven on a trace
recorded on the chip (``data_sala/trace_sample.json``).

    python -m pytest benchmark/tests/test_rehearse_sala.py -q
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_sala")
sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce  # noqa: E402

COUNTED = ("decode_batch_mean.sala", "kv_pool_fill_share.sala",
           "sparse_read_share.sala", "state_pool_fill_share.sala",
           "itl_p95_s.sala", "ttft_p90_s.sala",
           "setup_programs", "window_compiles")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", "tiny-sala.longdoc", "--seed", "2147483659",
           "--seconds", "2", "--trace", str(trace), "--rehearse", DATA]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_cell_runs_checks_and_counts():
    detail, r = run(trace=1)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["rehearsal"] is True and r["device"]["platform"] == "cpu"
    chk = detail["check"]
    # prompts of 100 and 70 tokens against dense_len 64: the check selected
    assert chk["ok"] and chk["requests"] == 2
    assert chk["greedy_gap"] <= chk["tolerance"] >= chk["logprob_err"]
    # what the check reads out of the engine's cache, in float32: the
    # reference's blocks, states and compressed keys, and the counted pages
    assert chk["selection_overlap"] == 1.0
    assert chk["state_err"] <= chk["limits"]["state_err_max"]
    assert chk["kc_err"] <= chk["limits"]["kc_err_max"]
    assert chk["pages_on_device"] == chk["pages_counted"] > 0
    for name in COUNTED:
        assert name in r["metrics"], name
    assert r["metrics"]["window_compiles"]["value"] == 0
    # two programs: the decode window and the one extend chunk (+ small ones)
    assert 0 < r["metrics"]["sparse_read_share.sala"]["value"] < 100
    assert r["metrics"]["state_pool_fill_share.sala"]["value"] == 100
    assert chk["sparse_blocks_read"] < chk["sparse_blocks_live"]
    assert chk["n_prefill_chunks"] > 0


def test_untraced_run_reports_end_to_end():
    _, r = run(trace=0)
    assert r["correct"]
    assert r["metrics"]["out_tok_per_s"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0


def test_control_comes_out_not_correct():
    """``control_sala.py``: the same requests against the reference in the
    precision below fail the check, against the reference as stated pass."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_sala.py"), "--seed", "5",
         "--rehearse", DATA, "--workload", "tiny-sala.longdoc"],
        cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["as_it_has_to"] and out["sound"]["ok"] and not out["control"]["ok"]
    assert out["control"]["state_err"] > out["control"]["limits"]["state_err_max"]
