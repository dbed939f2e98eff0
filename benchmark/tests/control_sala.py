"""The control of ``sala-d12.longdoc-closed``'s correctness check: the same
two requests through the same engine, compared once with the plain reference
as the configuration states it (float32: has to be ok) and once with the
reference in the precision below (float8 weights, selection scores from
bf16 operands, a bf16 lightning state: has to come out NOT ok, by at least
one of the check's limits).  Prints both comparisons; exits 0 only if both
came out as they have to.  On the chip, at the timed widths:

    chiprun -- python3 benchmark/tests/control_sala.py --seed 2147483777

``test_rehearse_sala.py`` runs it on the CPU at the tiny preset
(``--rehearse benchmark/tests/data_sala --workload tiny-sala.longdoc``).
"""

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, reference_sala, sala_serve_runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="sala-d12.longdoc-closed")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", default=None, metavar="DIR")
    args = ap.parse_args(argv)
    data = args.rehearse or harness.HERE
    manifest = harness.load_json(os.path.join(args.rehearse or ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, args.workload, data, seed=args.seed,
                             seconds=0.0, trace=False, rehearse=bool(args.rehearse))
    harness.require_devices(cell)
    harness.enable_compile_cache()
    engine, _ = sala_serve_runner.build_engine(cell, harness.Setup(T0))
    seen = sala_serve_runner.observe(engine, cell)
    sound = sala_serve_runner.compare(seen, engine.params, cell.config)
    control = sala_serve_runner.compare(seen, engine.params, cell.config,
                                        low=reference_sala.LOW)
    engine.close()
    as_it_has_to = bool(sound["ok"] and not control["ok"])
    print(json.dumps({"seed": args.seed, "as_it_has_to": as_it_has_to,
                      "sound": sound, "control": control}))
    return 0 if as_it_has_to else 1


if __name__ == "__main__":
    sys.exit(main())
