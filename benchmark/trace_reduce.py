"""From the profiler's trace to the numbers the per-layer metrics read.

Two steps, so that the second can be tested on a few kB of recorded events
(``benchmark/tests``):

1. ``read_xplane(path)`` reads the ``.xplane.pb`` the JAX profiler wrote
   (``jax.profiler.ProfileData``, nothing but JAX) into the reduction's own
   intermediate form, ``Events``: per device the program spans (the ``XLA
   Modules`` line) and the operation spans (``XLA Ops`` and ``Async XLA
   Ops``), each operation cut down to its name, its HLO opcode and its
   first result shape; and the host's spans by thread.
2. ``Reduced(events, ...)`` holds what the metrics read: seconds busy per device
   (the union of operation intervals), the idle gaps and what the host was
   doing in each, seconds per program and per kind of operation, and the
   helpers the readers under ``benchmark/metrics`` call.

All times inside are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics

# HLO opcodes that only contain other operations: their time is their
# children's, so they are left out of the per-operation sums
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_SUFFIX = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text: str) -> tuple[str, str, list[int]]:
    """``(name, opcode, first result shape)`` of one operation event, whose
    name in the trace is its HLO instruction: ``%fusion.3 = bf16[4,8]{...}
    fusion(...)``.  A name that is no instruction is its own opcode."""
    head, sep, rest = text.partition(" = ")
    name = head.lstrip("%")
    if not sep:
        return name, _SUFFIX.sub("", name), []
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else _SUFFIX.sub("", name)
    if opcode == "custom-call":
        # a Pallas kernel is the custom call whose target is Mosaic's; XLA
        # has custom calls of its own (named custom-call.N), which are not
        t = _TARGET.search(rest)
        if (t.group(1) != "tpu_custom_call") if t else name.startswith("custom-call"):
            opcode = "xla-custom-call"
    s = _SHAPE.search(rest)
    shape = [int(x) for x in s.group(2).split(",") if x] if s else []
    return name, opcode, shape


def kind_of(name: str, opcode: str) -> str:
    """The label an operation is summed under: a kernel is
    ``tpu_custom_call``, a fusion keeps the stem of its name
    (``convolution_add_fusion``), anything else its opcode."""
    if opcode == "custom-call":
        return "tpu_custom_call"
    if opcode == "fusion":
        return _SUFFIX.sub("", name)
    return opcode


def program_of(module_name: str) -> str:
    """``jit__window_impl(4828526723788095013)`` -> ``_window_impl``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class Events:
    """The intermediate form.  ``devices[i]`` has ``modules``: [name, start,
    dur] and ``ops``: [name, opcode, shape, start, dur, is_async];
    ``host``: [thread, name, start, dur]."""

    devices: list[dict]
    host: list[list]
    raw: dict = dataclasses.field(default_factory=dict)  # kind -> one event
    #   name as the trace had it, kept so that parse_op is tested on the real thing

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(devices=d["devices"], host=d["host"], raw=d.get("raw", {}))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> Events:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, raw = [], [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"id": int(plane.name.rsplit(":", 1)[1]), "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        dev["modules"].append([e.name, int(e.start_ns), int(e.duration_ns)])
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    is_async = line.name != "XLA Ops"
                    for e in line.events:
                        name, opcode, shape = parse_op(e.name)
                        raw.setdefault(kind_of(name, opcode), e.name[:1500])
                        dev["ops"].append([name, opcode, shape, int(e.start_ns),
                                           int(e.duration_ns), is_async])
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                thread = line.name
                for e in line.events:
                    host.append([thread, e.name, int(e.start_ns), int(e.duration_ns)])
    devices.sort(key=lambda d: d["id"])
    return Events(devices=devices, host=host, raw=raw)


def sample_events(events: Events, per_kind: int = 3, host_min_ns: int = 50_000) -> dict:
    """A few kB of a trace in the intermediate form: every program span, a
    few operations of each kind, and the host spans long enough to matter.
    What ``benchmark/tests/data`` holds was cut from a chip trace this way."""
    devices = []
    for dev in events.devices:
        seen: dict[str, int] = {}
        ops = []
        for op in dev["ops"]:
            k = kind_of(op[0], op[1]) + ("/async" if op[5] else "")
            if seen.get(k, 0) < per_kind:
                seen[k] = seen.get(k, 0) + 1
                ops.append(op)
        devices.append({"id": dev["id"], "modules": dev["modules"][:200], "ops": ops})
    host = [h for h in events.host if h[3] >= host_min_ns][:400]
    return {"devices": devices, "host": host, "raw": events.raw}


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` of possibly nested intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list[tuple[int, int]]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _is_collective(opcode: str, name: str) -> bool:
    return any(opcode.startswith(c) or name.startswith(c) for c in COLLECTIVES)


class Reduced:
    """One traced window, reduced.  Seconds everywhere."""

    def __init__(self, events: Events, n_devices: int, window_s: float):
        self.events = events
        self.window_s = float(window_s)
        self.devices = events.devices[:n_devices] if n_devices else events.devices
        busy = []
        self._busy_iv = []
        # synchronous, non-container operations by start, once per device
        self._sync_ops = [
            sorted((op for op in dev["ops"]
                    if not op[5] and op[1] not in CONTAINERS), key=lambda o: o[3])
            for dev in self.devices]
        for dev in self.devices:
            # the device is busy while a program's operation runs; program
            # spans stand in where a trace carries no operation line
            iv = [(op[3], op[3] + op[4]) for op in dev["ops"] if not op[5]]
            if not iv:
                iv = [(m[1], m[1] + m[2]) for m in dev["modules"]]
            merged = union(iv)
            self._busy_iv.append(merged)
            busy.append(length(merged) / 1e9)
        # averaged over the chips used, as the driver reads it
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    # ---- programs ----
    def module_durations(self, program: str, device: int = 0) -> list[float]:
        """Device seconds of each run of ``program`` (``_window_impl``)."""
        return [m[2] / 1e9 for m in self.devices[device]["modules"]
                if program_of(m[0]) == program]

    def module_spans(self, program: str, device: int = 0) -> list[tuple[int, int]]:
        return [(m[1], m[1] + m[2]) for m in self.devices[device]["modules"]
                if program_of(m[0]) == program]

    def median_module_s(self, program: str) -> float | None:
        d = self.module_durations(program)
        return statistics.median(d) if d else None

    # ---- operations ----
    def ops_in(self, spans, device: int = 0):
        """Synchronous, non-container operations that start inside one of
        the merged ``spans``."""
        spans = union(spans)
        out, j = [], 0
        for op in self._sync_ops[device]:
            while j < len(spans) and spans[j][1] <= op[3]:
                j += 1
            if j < len(spans) and spans[j][0] <= op[3]:
                out.append(op)
        return out

    def kernel_events(self, program: str | None = None, device: int = 0):
        """``(shape, seconds)`` of every Pallas kernel call (a
        ``custom-call``), inside ``program``'s spans when one is named."""
        if program is None:
            ops = self._sync_ops[device]
        else:
            ops = self.ops_in(self.module_spans(program, device), device)
        return [(op[2], op[4] / 1e9, op[0]) for op in ops if op[1] == "custom-call"]

    def exposed_collective_s(self, device: int = 0) -> tuple[float, float]:
        """``(exposed, total)`` seconds of collectives on one chip: total is
        the union of every collective's interval (its asynchronous span
        where it has one), exposed the part of it during which no other
        operation ran."""
        coll, compute = [], []
        for op in self.devices[device]["ops"]:
            iv = (op[3], op[3] + op[4])
            if _is_collective(op[1], op[0]):
                coll.append(iv)
            elif not op[5] and op[1] not in CONTAINERS:
                compute.append(iv)
        coll_u, comp_u = union(coll), union(compute)
        return length(subtract(coll_u, comp_u)) / 1e9, length(coll_u) / 1e9

    # ---- breakdown ----
    def device_ops(self, top: int = 10) -> list[list]:
        """Seconds per ``<program>/<kind of operation>`` on chip 0."""
        if not self.devices:
            return []
        dev = self.devices[0]
        mods = sorted(dev["modules"], key=lambda m: m[1])
        sums: dict[str, float] = {}
        j = 0
        for op in self._sync_ops[0]:
            while j < len(mods) and mods[j][1] + mods[j][2] <= op[3]:
                j += 1
            prog = (program_of(mods[j][0])
                    if j < len(mods) and mods[j][1] <= op[3] else "no_program")
            key = prog + "/" + kind_of(op[0], op[1])
            sums[key] = sums.get(key, 0.0) + op[4] / 1e9
        if not sums:  # no operation line: programs alone
            for m in mods:
                key = program_of(m[0])
                sums[key] = sums.get(key, 0.0) + m[2] / 1e9
        return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """The longest idle gaps of chip 0 between its first and last
        operation, each named by the innermost host span over its middle:
        one of the Python thread's if any covers it, else any thread's."""
        merged = self._busy_iv[0] if self._busy_iv else []
        gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])
                       if b[0] - a[1] >= 1000),  # a microsecond and more
                      reverse=True)[:top]
        out = []
        for dur, s, e in gaps:
            mid = (s + e) // 2
            best = None
            for thread, name, hs, hd in self.events.host:
                if hs <= mid < hs + hd:
                    py = thread.startswith("python")
                    cand = (not py, hd, name if name.startswith("_bench:")
                            else f"{thread}:{name}")
                    if best is None or cand < best:
                        best = cand
            out.append([best[2] if best else "no_host_span_covers_it", dur / 1e9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}
