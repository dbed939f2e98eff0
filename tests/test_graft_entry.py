"""Driver-contract tests for __graft_entry__.py.

The driver (a) compile-checks ``entry()`` single-chip and (b) runs
``dryrun_multichip(n)`` with a virtual n-device CPU platform.  These tests pin
both contracts — including that dryrun self-arms its device count in a fresh
interpreter with NO env vars set (the driver may call it that way).
"""

import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_entry_returns_jittable_forward():
    import jax

    sys.path.insert(0, str(REPO))
    try:
        import __graft_entry__ as g
    finally:
        sys.path.pop(0)
    fn, args = g.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (args[1].shape[0], 10)


def test_dryrun_multichip_self_arms_in_clean_subprocess():
    # Strip every platform/device hint from the env: the dryrun must build
    # its own 8-device CPU mesh.
    import os

    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
