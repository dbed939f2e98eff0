"""Mosaic or the interpreter: how this package's Pallas kernels lower.

Mosaic lowers only on TPU.  The Pallas interpreter is how the CPU test
suite drives the same kernel bodies, but it is never chosen by guessing: a
process that lost its chip would otherwise run interpreted kernels and
still print a number.  So the interpreter is the CALLER's explicit choice —
``interpret=True`` on a call, or :func:`set_interpret` once per process
(``tests/conftest.py``, ``chip_smoke.py --cpu-dry-run``) — and a kernel
called on a non-TPU backend without that choice is an error naming the
backend.
"""

from __future__ import annotations

import jax

_forced = False


def set_interpret(on: bool) -> None:
    """Process-wide: kernels called with ``interpret=None`` run in the
    Pallas interpreter.  For tests and dry runs; never on a path whose
    numbers are reported as the device's."""
    global _forced
    _forced = bool(on)


def interpret_forced() -> bool:
    return _forced


def resolve_interpret(interpret: bool | None) -> bool:
    """The ``interpret`` flag a ``pallas_call`` gets: the caller's explicit
    value, else the process-wide choice, else Mosaic — which must be on
    TPU."""
    if interpret is not None:
        return interpret
    if _forced:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernel called on the {backend!r} backend: Mosaic "
            "compiles for TPU only.  To run the Pallas interpreter on "
            "purpose pass interpret=True or call "
            "ops.interpret.set_interpret(True) first (the CPU tests do); "
            "otherwise check why this process has no TPU")
    return False
