"""The runner rehearsed end to end on the CPU at a tiny size: every kind of
cell through ``benchmark/run.py --rehearse``, the four-chip cell on four
virtual devices.  A rehearsal proves paths, arguments and control flow; its
numbers are never measurements (the result says ``"rehearsal": true`` and
names the CPU).

    python -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")


def run(workload, trace=0, devices=1, seconds=1, rehearse=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483659",
           "--seconds", str(seconds), "--trace", str(trace)]
    if rehearse:
        cmd += ["--rehearse", DATA]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True)


def result(p):
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,devices,metric", [
    ("tiny.pretrain", 1, "train_tok_per_s_per_chip"),
    ("tiny.pretrain-dp4", 4, "train_tok_per_s_per_chip"),
    ("tiny.closed", 1, "out_tok_per_s"),
    ("tiny.paced", 1, "ttft_p90_s"),
])
def test_cell_runs_and_is_correct(workload, devices, metric):
    r = result(run(workload, devices=devices))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["rehearsal"] is True and r["device"]["platform"] == "cpu"
    assert r["device"]["count"] == devices
    assert r["metrics"][metric]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_per_layer_metrics_only():
    r = result(run("tiny.paced", trace=1, seconds=2))
    assert "ttft_p90_s" not in r["metrics"]
    assert r["metrics"]["window_compiles"]["value"] == 0
    assert r["metrics"]["gen_late_p90_s"]["value"] < 0.05
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_is_an_error_not_a_cpu_number():
    """Without ``--rehearse`` a run that finds no TPU exits non-zero and
    prints no result."""
    p = run(json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"][0]["name"],
            rehearse=False)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_the_replayed_trace_does_not_depend_on_the_seed():
    sys.path.insert(0, ROOT)
    from benchmark import traffic

    tr = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "code-paced.json")))
    a, b = traffic.open_schedule(tr, 60.0), traffic.open_schedule(tr, 60.0)
    assert all((a[k] == b[k]).all() for k in a)
    rate = len(a["due"]) / 60.0
    assert abs(rate - tr["arrivals"]["rate_per_s"]) < 0.5
    assert a["prompt_len"].min() >= 256 and a["prompt_len"].max() <= 4000
    assert (a["prompt_len"] + a["max_new"]).max() <= 4096
    # scaling the rate replays the same requests faster
    fast = traffic.open_schedule(dict(tr, rate_scale=2.0), 30.0)
    n = min(len(fast["due"]), len(a["due"]))
    assert (fast["prompt_len"][:n] == a["prompt_len"][:n]).all()
    assert abs(fast["due"][n - 1] * 2.0 - a["due"][n - 1]) < 1e-9
    # other seeds give other tokens, the same seed the same
    t1 = traffic.prompt_tokens(2147483659, 3, 50, 49152)
    assert (t1 == traffic.prompt_tokens(2147483659, 3, 50, 49152)).all()
    assert (t1 != traffic.prompt_tokens(7, 3, 50, 49152)).any() and t1.min() >= 1
