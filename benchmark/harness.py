"""What every runner of the benchmark shares: the cell, the set-up clock,
the device check, the compile counter's window, the profiler window and the
discovery of per-layer metric readers.

The harness is driven by data.  A cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration is ``benchmark/configs/<config>.json``,
its traffic ``benchmark/traffic/<traffic>.json``, its runner the module
``benchmark/<runner>_runner.py`` that the configuration names, and each
per-layer metric ``benchmark/metrics/<name>.py`` (or, for a name split by a
suffix such as ``device_idle_share.train``, the file of the part before the
first dot).  A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One run of one cell: what the manifest and the command line fix."""

    name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool = False  # CPU rehearsal (tests): never a measurement
    dump_events: str | None = None  # write the reduced trace here (debug)

    def jax_seed(self) -> int:
        """``--seed`` folded into what a 32-bit PRNG key takes."""
        return int(self.seed) % (2**31 - 1)


def load_cell(manifest: dict, workload: str, data_dir: str = HERE, **kw) -> Cell:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise SystemExit(f"no workload {workload!r} in the manifest")
    return Cell(
        name=workload, traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(os.path.join(data_dir, "configs", w["config"] + ".json")),
        traffic=load_json(os.path.join(data_dir, "traffic", w["traffic"] + ".json")),
        **kw)


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    """The manifest's metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


class Setup:
    """Itemised set-up time.  ``t0`` is taken at the top of ``run.py``,
    before anything heavy is imported, so ``total()`` is process start to
    ready as a user of the benchmark would clock it."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.items: dict[str, float] = {}
        self._last = t0

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.items[name] = self.items.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        return self._last - self.t0


def require_devices(cell: Cell):
    """The devices the cell runs on, or exit: a measurement never falls
    back to the CPU, and never runs on fewer chips than the cell asks."""
    import jax

    devs = jax.devices()
    if not cell.rehearse and devs[0].platform != "tpu":
        raise SystemExit(
            f"no accelerator: jax sees {devs[0].platform}; a measurement "
            "needs the TPU")
    if len(devs) < cell.chips:
        raise SystemExit(
            f"cell {cell.name} needs {cell.chips} chips, jax sees {len(devs)}")
    return devs[:cell.chips]


def enable_compile_cache() -> None:
    """The program's persistent compilation cache, before the benchmark's
    own first compile (weights, reference): where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.cache/xla``."""
    from distributed_tensorflow_ibm_mnist_tpu.utils.compile_cache import (
        enable_compile_cache as enable,
    )

    enable()


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def compile_tracker():
    from distributed_tensorflow_ibm_mnist_tpu.utils.tracing import CompileTracker

    return CompileTracker.install()


def compile_delta(after: dict, before: dict) -> dict:
    return {"programs": after["n_compiled_programs"] - before["n_compiled_programs"],
            "compile_s": after["compile_time_s"] - before["compile_time_s"],
            "cache_hits": (after["persistent_cache_hits"]
                           - before["persistent_cache_hits"])}


def percentile(xs, q: float) -> float | None:
    """numpy's linear percentile, as ``serving/stats.py`` takes it."""
    import numpy as np

    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


class ProfilerWindow:
    """The device trace of one window: started and stopped by the runner,
    reduced once, removed from disk.  The trace lives under the checkout's
    ``.cache`` at a fixed path per cell."""

    def __init__(self, cell: Cell):
        self.on = bool(cell.trace)
        self.dir = os.path.join(ROOT, ".cache", "bench_trace", cell.name)
        self.dump = cell.dump_events
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # no Python function tracer: it slows the host loop under test and
        # buries the TraceAnnotation spans the idle gaps are named by
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.on or self.t0 is None or self.t1 is not None:
            return
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, n_devices: int):
        """The reduced trace (``trace_reduce.Reduced``), or None untraced."""
        if not self.on or self.t1 is None:
            return None
        from benchmark import trace_reduce

        events = trace_reduce.read_xplane(trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        if self.dump:
            os.makedirs(os.path.dirname(os.path.abspath(self.dump)), exist_ok=True)
            with open(self.dump, "w") as f:
                json.dump(trace_reduce.sample_events(events), f)
        return trace_reduce.Reduced(events, n_devices, self.t1 - self.t0)


def annotate(name: str):
    """A host span in the profiler's own trace, so that an idle gap on the
    device carries the name of what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation("_bench:" + name)


def find_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or
    the file of the name's part before its first dot."""
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "benchmark_metric_" + stem.replace(".", "_").replace("-", "_"), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise SystemExit(f"no reader for per-layer metric {name!r} under benchmark/metrics/")


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may look at."""

    metric: str          # the metric's full name, suffix included
    cell: Cell
    counters: dict       # the runner's counters and host-side statistics
    trace: object | None  # trace_reduce.Reduced of the traced window
    device: dict         # device_record()
