"""Multi-host TPU process bootstrap.

Replaces the reference's cluster-resolution layer (SURVEY.md §2.2:
ClusterSpec/env -> "TPU metadata auto-detection ... in JAX: jax.devices() +
distributed init").  On a multi-host TPU slice, every host runs the same
binary; ``jax.distributed.initialize()`` discovers coordinator/peers from the
TPU metadata (or explicit args for non-TPU clusters) and joins the slice's
DCN bootstrap ring.  After that, ``jax.devices()`` spans the whole slice and
the in-graph ICI collectives need no further configuration — there is no
analog of the reference's per-step gRPC variable traffic.
"""

from __future__ import annotations

import jax


def bootstrap(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> dict:
    """Join (or skip, if single-process) the multi-host runtime.

    With no arguments on a TPU pod slice, jax.distributed.initialize() reads
    the TPU metadata; on CPU/GPU clusters pass the explicit triple.  Safe to
    call in single-process runs: initialization is skipped when there is
    nothing to join.  Returns a summary dict for logging.
    """
    multi = num_processes is not None and num_processes > 1
    if multi or coordinator_address is not None:
        # CPU clusters: the default (no-op) CPU collectives layer cannot run
        # cross-process computations ("Multiprocess computations aren't
        # implemented on the CPU backend") — arm the gloo TCP collectives
        # BEFORE the backend client exists.  TPU/GPU ignore this flag.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
