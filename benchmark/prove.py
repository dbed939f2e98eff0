"""Prove a cell as the benchmark's contract asks: a first run that compiles,
two sets of runs with the same seeds in both, one traced run, and for each
end-to-end metric the spread of each set (the distance between the first
and third quartile of ``statistics.quantiles(values, n=4)`` over the median).

    python3 benchmark/prove.py --workload <name> [--runs 3] [--seconds S] [--out DIR]

Every run is a child process, one after another: this parent never touches
JAX, so each child has the chip to itself.  Meant for ``chiprun``; writes
every run's two last lines to ``<out>/<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (2147483659, 7, 3000000019, 11, 4000000007, 13)  # large ones too


def one(workload, seed, seconds, trace, extra=()):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    t = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    rec = {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": round(time.time() - t, 1)}
    if p.returncode != 0 or len(lines) < 2:
        rec["stderr"] = p.stderr[-3000:]
        rec["stdout"] = p.stdout[-1000:]
    else:
        rec["detail"], rec["result"] = json.loads(lines[-2]), json.loads(lines[-1])
    return rec


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--no-first", action="store_true",
                    help="the cache is warm: no separate compiling run")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, args.workload + ".jsonl"), "a")

    def run(tag, seed, trace, extra=()):
        rec = {"tag": tag, "seconds": seconds,
               **one(args.workload, seed, seconds, trace, extra)}
        log.write(json.dumps(rec) + "\n")
        log.flush()
        brief = {k: v["value"] for k, v in rec.get("result", {}).get("metrics", {}).items()}
        print(tag, seed, "rc", rec["rc"], rec["wall_s"], "s", json.dumps(brief), flush=True)
        if "detail" in rec:
            print("   ", json.dumps(rec["detail"]), flush=True)
        if rec["rc"] != 0:
            print(rec.get("stderr", "")[-3000:], flush=True)
        return rec

    if not args.no_first:
        full, seconds = seconds, min(seconds, 5)  # it is there to compile
        if run("first", SEEDS[0], 0)["rc"] != 0:
            return 1
        seconds = full
    sets = {"A": [], "B": []}
    for tag in sets:
        for seed in SEEDS[:args.runs]:
            rec = run(tag, seed, 0)
            if rec["rc"] != 0:
                return 1
            sets[tag].append(rec["result"]["metrics"])
    for name in sets["A"][0]:
        a = [m[name]["value"] for m in sets["A"]]
        b = [m[name]["value"] for m in sets["B"]]
        print(f"SPREAD {name}: A median {statistics.median(a):.6g} spread {spread(a):.4%} | "
              f"B median {statistics.median(b):.6g} spread {spread(b):.4%} | "
              f"B/A {statistics.median(b) / statistics.median(a) - 1:+.4%}", flush=True)
    if not args.no_trace:
        rec = run("trace", SEEDS[1], 1, ("--dump-events", os.path.join(
            args.out, args.workload + ".events.json")))
        if "result" in rec:
            print(json.dumps(rec["result"]), flush=True)
        return rec["rc"]
    return 0


if __name__ == "__main__":
    sys.exit(main())
