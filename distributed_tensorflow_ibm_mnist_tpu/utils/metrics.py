"""Structured metric emission: JSONL (stdout and/or file) + TensorBoard.

Replaces the reference's observability layer (SURVEY.md §5 "Metrics /
logging": ``print``/``tf.logging`` of step, loss, accuracy, steps/sec).
Emits exactly the metrics of record from BASELINE.json:2 —
``images_per_sec_per_chip`` and wall-clock-to-target-accuracy — as
machine-readable JSON lines, with optional TensorBoard event files.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, IO


def _sanitize(v: Any) -> Any:
    """JSON-safe metric values: numerics become floats, and non-finite
    floats become None — ``json.dumps`` would otherwise emit bare ``NaN`` /
    ``Infinity`` tokens, which are NOT JSON and break every strict consumer
    of the log (a diverged loss must not corrupt the metrics file it is
    being recorded in).  Recurses through dicts/lists/tuples so nested
    blocks (a record's comparison sections) get the same guarantee."""
    if isinstance(v, dict):
        return {k: _sanitize(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_sanitize(x) for x in v]
    if not isinstance(v, (str, bool)) and hasattr(v, "__float__"):
        v = float(v)
    if isinstance(v, float) and not math.isfinite(v):
        return None
    return v


class MetricWriter:
    """JSON-lines metric writer; one record per event.

    Records carry a monotonic ``t`` (seconds since writer creation) so
    time-to-accuracy can be reconstructed from the log alone.  Usable as a
    context manager — ``with MetricWriter(path) as w: ...`` closes the file
    handle (and the TensorBoard writer) even when the body raises, so a
    crashing run cannot leak the handle or lose buffered events.
    """

    def __init__(self, path: str | None = None, stdout: bool = True, tensorboard_dir: str | None = None,
                 fsync: bool = False):
        self._file: IO[str] | None = open(path, "a") if path else None
        self._stdout = stdout
        # fsync=True makes each record crash-durable (survives SIGKILL):
        # every write() fsyncs the file.  Off by default — flush-only is
        # enough for normal runs and an fsync per record is not free.
        self._fsync = bool(fsync)
        self._t0 = time.perf_counter()
        self._tb = None
        self._closed = False
        if tensorboard_dir:
            try:
                from tensorboardX import SummaryWriter

                self._tb = SummaryWriter(tensorboard_dir)
            except Exception:
                self._tb = None

    def write(self, kind: str, step: int | None = None, **metrics: Any) -> dict[str, Any]:
        if self._closed:
            # fail HERE with the actual problem, not three frames deep with
            # "ValueError: I/O operation on closed file" from the file handle
            raise RuntimeError(
                f"MetricWriter is closed — write({kind!r}) after close() "
                "would lose the record; keep the writer open for the "
                "component's lifetime or create a new one")
        record = {"kind": kind, "t": round(time.perf_counter() - self._t0, 4)}
        if step is not None:
            record["step"] = int(step)
        record.update({k: _sanitize(v) for k, v in metrics.items()})
        line = json.dumps(record)
        if self._stdout:
            print(line, flush=True)
        if self._file:
            self._file.write(line + "\n")
            self._file.flush()
            if self._fsync:
                os.fsync(self._file.fileno())
        if self._tb and step is not None:
            for k, v in record.items():
                if k not in ("kind", "t", "step") and isinstance(v, (int, float)) and not isinstance(v, bool):
                    self._tb.add_scalar(f"{kind}/{k}", v, step)
        return record

    def close(self) -> None:
        """Release the file/TensorBoard handles.  Idempotent: a writer
        shared across components (trainer + engine) may see close() from
        more than one shutdown path."""
        if self._closed:
            return
        self._closed = True
        if self._file:
            self._file.close()
        if self._tb:
            self._tb.close()

    def __enter__(self) -> "MetricWriter":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
