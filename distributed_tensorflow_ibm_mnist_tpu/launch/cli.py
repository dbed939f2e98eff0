"""Training CLI: preset + overrides -> Trainer.

Replaces the reference's ``tf.app.flags`` entry point (SURVEY.md §3.1) minus
the role/cluster flags that SPMD makes obsolete.  Usage:

    python -m distributed_tensorflow_ibm_mnist_tpu.launch.cli \
        --preset mnist_lenet_1chip --set epochs=5 --set lr=5e-4

``--set key=value`` overrides any RunConfig field (values parsed as Python
literals when possible, else kept as strings).
"""

from __future__ import annotations

import argparse
import ast
import json
import sys

from distributed_tensorflow_ibm_mnist_tpu.utils.config import PRESETS, RunConfig, get_preset


def _parse_override(kv: str) -> tuple[str, object]:
    if "=" not in kv:
        raise argparse.ArgumentTypeError(f"override {kv!r} must be key=value")
    key, raw = kv.split("=", 1)
    try:
        value = ast.literal_eval(raw)
    except (ValueError, SyntaxError):
        value = raw
    return key, value


def build_config(argv: list[str] | None = None) -> RunConfig:
    return _build(argv)[0]


def _build(argv: list[str] | None = None) -> tuple[RunConfig, argparse.Namespace]:
    parser = argparse.ArgumentParser(
        prog="distributed_tensorflow_ibm_mnist_tpu.launch.cli",
        description="TPU-native trainer (see BASELINE.md for the preset configs)",
    )
    parser.add_argument(
        "--preset", choices=sorted(PRESETS), default=None,
        help="named benchmark config from BASELINE.json:6-12",
    )
    parser.add_argument(
        "--set", dest="overrides", action="append", default=[], type=_parse_override,
        metavar="KEY=VALUE", help="override any RunConfig field (repeatable)",
    )
    parser.add_argument(
        "--resume", action="store_true",
        help="restore the latest checkpoint from checkpoint_dir before training",
    )
    parser.add_argument(
        "--profile", default=None, metavar="DIR",
        help="capture an XLA/TPU profile of the steady-state epochs into DIR "
        "(TensorBoard profile plugin format); shorthand for "
        "--set profile_dir=DIR",
    )
    parser.add_argument(
        "--throughput", type=int, default=None, metavar="EPOCHS",
        help="measure steady-state throughput/MFU over EPOCHS chained epochs "
        "(Trainer.measure_throughput) instead of training; prints one JSON line",
    )
    parser.add_argument(
        "--virtual-devices", type=int, default=None, metavar="N",
        help="dev machines: run on an N-device virtual CPU mesh instead of "
        "any attached accelerator (utils/hostmesh) — lets dp/tp/sp/pp "
        "configs run where only one (or no) chip is attached.  The "
        "accelerator is never opened; the printed `device` says cpu",
    )
    parser.add_argument(
        "--coordinator", default=None,
        help="multi-host: coordinator address for jax.distributed.initialize",
    )
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    args = parser.parse_args(argv)

    if args.coordinator or (args.num_processes or 0) > 1:
        from distributed_tensorflow_ibm_mnist_tpu.launch.tpu_vm import bootstrap

        info = bootstrap(args.coordinator, args.num_processes, args.process_id)
        print(json.dumps({"kind": "bootstrap", **info}), flush=True)

    config = get_preset(args.preset) if args.preset else RunConfig()
    overrides = dict(args.overrides)
    if args.resume:
        overrides["resume"] = True
    if args.profile:
        overrides["profile_dir"] = args.profile
    unknown = set(overrides) - set(config.to_dict())
    if unknown:
        parser.error(f"unknown config fields: {sorted(unknown)}")
    return config.replace(**overrides), args


def main(argv: list[str] | None = None) -> int:
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer

    config, args = _build(argv)
    if args.virtual_devices:
        # the flag IS the choice of platform: go straight to the CPU without
        # opening an accelerator client just to count its devices
        from distributed_tensorflow_ibm_mnist_tpu.utils.hostmesh import (
            ensure_virtual_cpu_devices,
        )

        ensure_virtual_cpu_devices(args.virtual_devices)
    trainer = Trainer(config)
    if args.throughput:
        if config.profile_dir:
            # profile the measurement region too (the compile epoch is
            # unavoidably in-trace here; fit() stages it out instead)
            from distributed_tensorflow_ibm_mnist_tpu.utils.profiling import trace

            with trace(config.profile_dir):
                out = trainer.measure_throughput(epochs=args.throughput)
        else:
            out = trainer.measure_throughput(epochs=args.throughput)
        print(json.dumps({"kind": "throughput", **out}), flush=True)
        return 0
    summary = trainer.fit()
    print(json.dumps({"kind": "final", **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
