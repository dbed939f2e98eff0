"""``insert_row_dev_ms``: the median device time of the landing program, on
an event list made by hand; nothing to read with no insert and with no
trace."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness, trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def admissions(inserts_ms):
    """One prefill of 30 ms and one window of 12 ms per admission, and an
    insert of the given length between them."""
    ops, modules = [], []
    for i, ms in enumerate(inserts_ms):
        base = i * 100 * MS
        modules.append(["jit__prefill_row(9)", base, 30 * MS])
        ops.append(["fusion.1", "fusion", [1, 1024, 3072], base, 30 * MS, False])
        modules.append(["jit__insert_row(11)", base + 31 * MS, int(ms * MS)])
        ops.append(["dynamic-update-slice_fusion.2", "fusion", [2816, 64, 2, 128],
                    base + 31 * MS, int(ms * MS), False])
        modules.append(["jit__window_impl(7)", base + 60 * MS, 12 * MS])
        ops.append(["fusion.3", "fusion", [64, 3072], base + 60 * MS, 12 * MS, False])
    return tr.Events(devices=[{"id": 0, "modules": modules, "ops": ops}], host=[])


def reading(inserts_ms):
    return types.SimpleNamespace(trace=tr.Reduced(admissions(inserts_ms), 1, 0.3))


@pytest.mark.parametrize("name", ["insert_row_dev_ms.closed", "insert_row_dev_ms.paced"])
def test_median_over_inserts_and_silence_without_one(name):
    read = harness.find_reader(name)
    assert read(reading([0.25, 0.75, 0.5])) == pytest.approx(0.5)
    assert read(reading([])) is None
    assert read(types.SimpleNamespace(trace=None)) is None
