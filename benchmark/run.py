"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its output, the result the driver reads.  The
line before it itemises the set-up.  See ``benchmark/harness.py`` for how a
cell, a traffic mix and a per-layer metric are found by name.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, before anything heavy is imported

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-events", default=None,
                    help="with --trace 1: write a sample of the reduced trace here")
    ap.add_argument("--rehearse", default=None, metavar="DIR",
                    help="CPU rehearsal for the tests: take BENCHMARK.json, "
                         "configs/ and traffic/ from DIR; never a measurement")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse:
        manifest = harness.load_json(os.path.join(args.rehearse, "BENCHMARK.json"))
        data_dir = args.rehearse
    else:
        manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
        data_dir = harness.HERE
    cell = harness.load_cell(
        manifest, args.workload, data_dir, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        rehearse=bool(args.rehearse), dump_events=args.dump_events)
    devs = harness.require_devices(cell)
    harness.enable_compile_cache()
    runner = importlib.import_module(f"benchmark.{cell.config['runner']}_runner")
    out = runner.run(cell, devs, harness.Setup(T0))

    device = harness.device_record(devs)
    if cell.trace:
        reduced = out["profiler"].reduce(len(devs))
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        reading = harness.Reading("", cell, out["counters"], reduced, device)
        values = {}
        for m in harness.metrics_of(manifest, "per_layer", cell.name):
            reading.metric = m["name"]
            v = harness.find_reader(m["name"])(reading)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                              "unit": m["unit"]}
                  for m in harness.metrics_of(manifest, "end_to_end", cell.name)}
    result = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": values, "device": device}
    if cell.trace:
        result["breakdown"] = reduced.breakdown()
    if cell.rehearse:
        result["rehearsal"] = True
    print(json.dumps({"setup": out["setup"], "check": out["check"]}))
    print(json.dumps(result))
    sys.stdout.flush()
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
