"""The persistent XLA compilation cache: where it lives, and the one place
that turns it on.

The cache is placed from OUTSIDE the program.  Where
``JAX_COMPILATION_CACHE_DIR`` is exported, jax adopts it at import and this
module sets no directory at all; where it is not, the cache lives at the
fixed ``<checkout>/.cache/xla``.  Never a temp dir, a pid or a timestamp:
the path is part of how a later process finds what an earlier one compiled,
so a directory that moves never hits.

Everything that wants warm compiles — ``Trainer``, ``InferenceEngine``
(hence every ``Replica`` factory), ``benchmark/run.py``, the scripts,
``chip_smoke.py`` — calls :func:`enable_compile_cache`; nothing else writes
jax's cache-directory option.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory the cache uses when it is on.

    Imports no jax, so a parent process that must stay off the chip
    (the ``chip_smoke.py`` parent) can inspect the cache's state.
    """
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".cache", "xla")


def enable_compile_cache(*, cpu: bool = False) -> str | None:
    """Turn the persistent cache on for this process (it is process-global).

    Returns the cache directory, or None when the cache was left off.
    Safe to call any number of times and after compiles have happened:
    the installed jax builds the cache lazily at the next compile.

    With ``JAX_COMPILATION_CACHE_DIR`` exported the cache is already on, on
    any backend — the exporter's choice.  Without it, accelerator backends
    get the fixed checkout directory and the CPU backend stays off unless
    ``cpu=True``: XLA:CPU reloads its AOT artifacts across machine-feature
    drift with "could lead to SIGILL" errors on stderr (seen here between
    two processes on ONE machine), and the test suite has no use for
    entries.  ``cpu=True`` is for CPU harnesses that measure the cache
    itself (warm replica respawns).

    The hot configs here compile in seconds but are re-run constantly
    (benchmarks, CI, presets), so entries are kept from 0.1 s of compile
    time up, not jax's 1 s.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        if not cpu and jax.default_backend() == "cpu":
            return None
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            raise OSError(
                f"cannot create the compile cache at {path} ({e}); export "
                f"{ENV_VAR} to place it somewhere writable") from e
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path
