"""``nemotron-d11.agent-closed`` rehearsed end to end on the CPU at a tiny
preset (hidden 64, layers ``MEMEMEM*EME``, 8 Mamba heads of 16, rank 1 of 4
holding 4 of 16 relu^2 experts, chunks of 32): the runner with its mixed
prompt lengths, the check against the plain reference, the control and the
counter readers.  A rehearsal proves paths, arguments and control flow; its
numbers are never measurements.  The readers that need a device trace are
driven on a trace recorded on the chip (``test_nemotron_readers.py``).

    python -m pytest benchmark/tests/test_rehearse_nemotron.py -q
"""

import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data_nemotron")
sys.path.insert(0, ROOT)

from benchmark import harness, mimo_serve_runner  # noqa: E402

COUNTED = ("decode_batch_mean.nemo", "kv_pool_fill_share.nemo",
           "state_pool_fill_share.nemo", "expert_held_share.nemo",
           "expert_load_peak_ratio.nemo", "setup_programs", "window_compiles")


def run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", "tiny-nemotron.agent", "--seed", "2147483659",
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
    # prompts of 9 and 75 tokens against chunks of 32, beside a filler in
    # each other slot; every request sampled at this size
    assert chk["ok"] and chk["requests"] == chk["sampled"] == 4
    assert chk["live_rows_min"] >= 2 and chk["routes_complete"]
    assert chk["greedy_gap"] <= chk["tolerance"] >= chk["logprob_err"]
    # what the check reads out of the engine's cache, in float32: the
    # reference's states (whole and head by head), convolution tails and
    # experts, the device's load against the engine's recorded choices, and
    # the counted tokens, row-steps and pairs
    assert chk["expert_overlap"] == 1.0 and chk["load_err"] == 0.0
    assert chk["state_err"] <= min(chk["limits"]["state_err_max"])
    assert chk["state_head_err"] <= min(chk["limits"]["state_head_err_max"])
    assert chk["conv_err"] <= min(chk["limits"]["conv_err_max"])
    assert chk["counted"] == chk["expected"]
    for name in COUNTED:
        assert name in r["metrics"], name
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert r["metrics"]["state_pool_fill_share.nemo"]["value"] == 100
    # 4 of 16 experts held: a quarter of the pairs under even routing
    assert 5 < r["metrics"]["expert_held_share.nemo"]["value"] < 60
    assert chk["expert_assignments_held"] == sum(map(sum, chk["expert_load"])) > 0
    assert chk["ssm_scan_tokens"] > 0 and chk["ssm_step_rows"] > 0
    assert chk["n_prefill_chunks"] > 0


def test_untraced_run_reports_end_to_end():
    _, r = run(trace=0)
    assert r["correct"]
    assert r["metrics"]["out_tok_per_s"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0


def test_control_comes_out_not_correct():
    """``control_nemotron.py``: the same requests against the lowered
    reference fail the check, against the reference as stated pass; a bf16
    state alone and float8 weights alone fail it each."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_nemotron.py"), "--seed", "5",
         "--rehearse", DATA, "--workload", "tiny-nemotron.agent"],
        cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["as_it_has_to"] and out["sound"]["ok"] and not out["control"]["ok"]
    lim = out["control"]["limits"]
    assert out["control"]["by_layer"]["state_err"][0] > lim["state_err_max"][0]
    assert out["alone"]["state"]["by_layer"]["state_err"][0] > lim["state_err_max"][0]
    assert not out["alone"]["state"]["ok"] and not out["alone"]["weights"]["ok"]


def test_the_cells_traffic_is_the_mixture_the_issue_names():
    """The real traffic file through ``mixed_tables``: 128 clients, 70% of
    prompts uniform in 512-4096 and 30% lognormal around 16384 clipped to
    8192-32768, answers uniform in 1024-4096, every request inside the
    engine's ``max_len``."""
    tr = harness.load_json(os.path.join(ROOT, "benchmark", "traffic", "agent-closed.json"))
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "nemotron3-super-serve-d11.json"))
    tab = mimo_serve_runner.mixed_tables(tr, 64)
    p, m = tab["prompt_len"], tab["max_new"]
    assert p.shape == m.shape == (128, 64) == (tr["clients"], 64)
    short = p <= 4096
    assert p[short].min() >= 512 and p[~short].min() >= 8192 and p.max() <= 32768
    assert 0.66 < short.mean() < 0.74
    assert 14500 < np.median(p[~short]) < 18000
    assert m[:, 1:].min() >= 1024 and m.max() <= 4096 and m[:, 0].min() >= 1
    assert (p + m).max() <= cfg["engine"]["max_len"]
    assert tr["prefill_chunk"] == cfg["engine"]["prefill_chunk"] == 2048
    assert cfg["engine"]["slots"] == tr["clients"] == 128
