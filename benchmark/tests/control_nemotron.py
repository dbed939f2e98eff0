"""The control of ``nemotron-d11.agent-closed``'s correctness check: the
same requests through the same engine, compared once with the plain
reference as the configuration states it (float32: has to be ok), and with
the reference lowered, each lowering alone and both together: float8
weights; a bf16 SSM state with bf16 step sizes and bf16 decays.  Each
lowered reading has to come out NOT ok, by at least one of the check's
limits.  Prints every comparison; exits 0 only if all came out as they
have to.  On the chip, at the timed widths:

    python3 benchmark/tests/control_nemotron.py --seed 2147483777  # one chip

``test_rehearse_nemotron.py`` runs it on the CPU at the tiny preset
(``--rehearse benchmark/tests/data_nemotron --workload tiny-nemotron.agent``).
"""

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, nemotron_serve_runner, reference_nemotron  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="nemotron-d11.agent-closed")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", default=None, metavar="DIR")
    ap.add_argument("--low", default=",".join(reference_nemotron.LOW),
                    help="what the control lowers or drops (comma-separated)")
    args = ap.parse_args(argv)
    data = args.rehearse or harness.HERE
    manifest = harness.load_json(os.path.join(args.rehearse or ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(manifest, args.workload, data, seed=args.seed,
                             seconds=0.0, trace=False, rehearse=bool(args.rehearse))
    harness.require_devices(cell)
    harness.enable_compile_cache()
    engine, _ = nemotron_serve_runner.build_engine(cell, harness.Setup(T0))
    seen = nemotron_serve_runner.observe(engine, cell)
    sound = nemotron_serve_runner.compare(seen, engine.params, cell.config)
    control = nemotron_serve_runner.compare(seen, engine.params, cell.config,
                                            low=tuple(args.low.split(",")))
    # each lowering on its own: each has to be refused too
    alone = {name: {k: v for k, v in nemotron_serve_runner.compare(
        seen, engine.params, cell.config, low=(name,)).items()
        if k in ("ok", "greedy_gap", "logprob_err", "state_err", "state_head_err",
                 "conv_err", "expert_overlap", "load_err", "by_layer")}
        for name in reference_nemotron.LOW}
    engine.close()
    as_it_has_to = bool(sound["ok"] and not control["ok"]
                        and not any(a["ok"] for a in alone.values()))
    print(json.dumps({"seed": args.seed, "as_it_has_to": as_it_has_to,
                      "sound": sound, "control": control, "alone": alone}))
    return 0 if as_it_has_to else 1


if __name__ == "__main__":
    sys.exit(main())
