"""Tracing / profiling: the subsystem the reference never had.

SURVEY.md §5 row 1: the reference's "profiler" was ``time.time()`` around
the loop.  TPU-native replacements here:

* :func:`trace` — capture an XLA/TPU profile (view in TensorBoard's profile
  plugin) around any code region;
* :func:`start_server` — on-demand profiling of a live job from another
  process (``jax.profiler``'s sampling path);
* :class:`TraceSession` — the same capture staged imperatively, for the
  trainer's ``profile_dir``.

Under any of them the program's own spans (``utils/tracing.host_span``:
every compile site, every phase of ``InferenceEngine.step()``) land in the
capture's host plane beside the device lines.
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile the enclosed region into ``log_dir`` (TensorBoard-readable)."""
    jax.profiler.start_trace(log_dir, create_perfetto_link=False)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_server(port: int = 9999):
    """Start the live profiling server; returns the server object."""
    return jax.profiler.start_server(port)


class TraceSession:
    """Imperatively-staged profile capture for loops that decide mid-flight
    where steady state begins.

    ``Trainer.fit`` (RunConfig.profile_dir / ``--profile``) starts the
    capture after the first epoch's fence — so the one-time XLA compile
    doesn't bury the steady-state timeline — and stops it after the last
    fetch.  :func:`trace` stays the one-shot context-manager form of the
    same thing.  ``stop`` is idempotent and safe to call without ``start``
    (error-path friendly).
    """

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.active = False

    def start(self) -> None:
        if not self.active:
            jax.profiler.start_trace(self.log_dir, create_perfetto_link=False)
            self.active = True

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
