"""Find the knee of an open-loop cell: the same replayed trace with its
offsets scaled to several rates, in one process (one set-up, one chip).

    python3 benchmark/sweep.py --workload sc2-3b.code-paced --rates 2,3,4,5,6 --seconds 30

For each rate: offered and completed requests a second, the backlog at the
window's middle and end, the tails.  The knee is the highest rate at which
nothing fails and the backlog at the end is no larger than at the middle,
give or take two requests.  Run once, when a cell is defined; the cell's
rate goes into its traffic file as a number.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, serve_runner  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests a second, comma-separated")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sweep.json"))
    ap.add_argument("--rehearse", default=None, metavar="DIR")
    args = ap.parse_args()
    data = args.rehearse or harness.HERE
    manifest = harness.load_json(os.path.join(args.rehearse or ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, args.workload, data, seed=args.seed,
                             seconds=args.seconds, trace=False,
                             rehearse=bool(args.rehearse))
    devs = harness.require_devices(cell)
    harness.enable_compile_cache()
    base_rate = float(cell.traffic["arrivals"]["rate_per_s"])

    cfg = cell.config
    engine, started = serve_runner.start_engine(cell, harness.Setup(T0))
    print(json.dumps({"setup_s": time.perf_counter() - T0, **started}), flush=True)

    rows = []
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c.seed = args.seed * 1000 + k  # other token ids, or the trie would hit
        c.traffic["rate_scale"] = rate / base_rate
        client = serve_runner.Replay(engine, c, cfg["vocab_size"])
        client.warm_in()
        out = client.window(harness.ProfilerWindow(c))
        # let the backlog of an overloaded rate drain before the next one
        serve_runner.drive(engine, lambda: not engine.has_work)
        n = out["notes"]
        row = {"rate_per_s": rate, "offered_per_s": n["offered_per_s"],
               "completed_per_s": n["completed_per_s"], "failed": out["failed"],
               "backlog_mid": n["backlog_mid"], "backlog_end": n["backlog_end"],
               "sustained": out["failed"] == 0 and n["backlog_end"] <= n["backlog_mid"] + 2,
               "ttft_p50_s": n["ttft_p50_s"], "ttft_p90_s": out["end_to_end"]["ttft_p90_s"],
               "itl_p50_s": n["itl_p50_s"], "itl_p95_s": out["end_to_end"]["itl_p95_s"],
               "decode_batch_mean": n["decode_batch_mean"],
               "gen_late_p90_s": out["counters"]["gen_late_p90_s"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    engine.close()
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    summary = {"workload": args.workload, "seconds": args.seconds, "seed": args.seed,
               "device": harness.device_record(devs), "rows": rows, "knee_per_s": knee}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
