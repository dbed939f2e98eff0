"""The control of ``mimo-d7.mixed-closed``'s correctness check: the same two
requests through the same engine, compared once with the plain reference as
the configuration states it (float32: has to be ok) and once with the
reference lowered (float8 weights, router logits from bf16 operands, no sink
in the window layers' softmax, no correction bias in the choice: has to come
out NOT ok, by at least one of the check's limits).  Prints both
comparisons; exits 0 only if both came out as they have to.  On the chip, at
the timed widths:

    chiprun -- python3 benchmark/tests/control_mimo.py --seed 2147483777

``test_rehearse_mimo.py`` runs it on the CPU at the tiny preset
(``--rehearse benchmark/tests/data_mimo --workload tiny-mimo.mixed``).
"""

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, mimo_serve_runner, reference_mimo  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="mimo-d7.mixed-closed")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", default=None, metavar="DIR")
    ap.add_argument("--low", default=",".join(reference_mimo.LOW),
                    help="what the control lowers or drops (comma-separated)")
    args = ap.parse_args(argv)
    data = args.rehearse or harness.HERE
    manifest = harness.load_json(os.path.join(args.rehearse or ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, args.workload, data, seed=args.seed,
                             seconds=0.0, trace=False, rehearse=bool(args.rehearse))
    harness.require_devices(cell)
    harness.enable_compile_cache()
    engine, _ = mimo_serve_runner.build_engine(cell, harness.Setup(T0))
    seen = mimo_serve_runner.observe(engine, cell)
    sound = mimo_serve_runner.compare(seen, engine.params, cell.config)
    control = mimo_serve_runner.compare(seen, engine.params, cell.config,
                                        low=tuple(args.low.split(",")))
    engine.close()
    as_it_has_to = bool(sound["ok"] and not control["ok"])
    print(json.dumps({"seed": args.seed, "as_it_has_to": as_it_has_to,
                      "sound": sound, "control": control}))
    return 0 if as_it_has_to else 1


if __name__ == "__main__":
    sys.exit(main())
