"""``paged_attn_dev_ms``: kernel milliseconds per decode window, on an event
list made by hand; nothing to read where the window holds no kernel."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness, trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def windows(kernel: bool):
    """Three windows of 10 ms; with ``kernel``, two kernel calls of 1 ms and
    0.5 ms in each, and one of 7 ms in a prefill that must not count."""
    ops, modules = [], []
    for base in (0, 20 * MS, 40 * MS):
        modules.append(["jit__window_impl(7)", base, 10 * MS])
        ops.append(["fusion.1", "fusion", [64, 3072], base, 4 * MS, False])
        if kernel:
            ops += [["paged_decode_attention.3", "custom-call", [64, 2, 16, 128],
                     base + 4 * MS, 1 * MS, False],
                    ["paged_decode_attention.4", "custom-call", [64, 2, 16, 128],
                     base + 6 * MS, MS // 2, False]]
    modules.append(["jit__prefill_row(9)", 60 * MS, 9 * MS])
    ops.append(["block_0.20", "custom-call", [24, 1024, 128], 61 * MS, 7 * MS, False])
    ops.sort(key=lambda op: op[3])
    return tr.Events(devices=[{"id": 0, "modules": modules, "ops": ops}], host=[])


def reading(kernel: bool):
    return types.SimpleNamespace(trace=tr.Reduced(windows(kernel), 1, 0.070))


def test_kernel_ms_per_window_and_silence_without_kernel():
    read = harness.find_reader("paged_attn_dev_ms.closed")
    assert harness.find_reader("paged_attn_dev_ms.paced")(reading(True)) == pytest.approx(1.5)
    assert read(reading(True)) == pytest.approx(1.5)
    assert read(reading(False)) is None
    assert read(types.SimpleNamespace(trace=None)) is None
