"""Virtual host-CPU device meshes for development and CI.

The SURVEY.md §4 test strategy — distributed behavior validated on an
N-device CPU platform instead of "run it on the cluster to find out" —
needs N CPU devices.  From the shell that is
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``;
this helper does the same from inside a process (the ``--virtual-devices``
CLI flag, the multi-device examples, the driver's multichip dry run).

It is never reached from ``Trainer``, ``InferenceEngine``, the benchmark
or ``chip_smoke.py``: a program that wants the chip must not
quietly leave it for virtual CPUs.  Where a caller does leave an
initialized accelerator backend, this says so on stderr.
"""

from __future__ import annotations

import sys


def backends_initialized() -> bool:
    """True once jax has built its backend clients.

    Unlike ``jax.devices()`` this never triggers initialization itself —
    so a caller can go straight to the CPU platform without first opening
    (and then discarding) the TPU client.
    """
    from jax._src import xla_bridge as xb

    return xb.backends_are_initialized()


def ensure_virtual_cpu_devices(n: int) -> int:
    """Force jax onto an ``n``-device (or more) CPU platform.

    Safe to call before or after ``import jax``.  Before the backends
    exist it only sets the platform options — no accelerator client is
    ever opened.  If backends were already initialized with too few CPU
    devices, or on an accelerator, they are cleared and rebuilt, which
    invalidates any live jax arrays created before the call; leaving an
    accelerator is announced on stderr.  Returns the resulting device
    count.
    """
    import jax

    if backends_initialized():
        devices = jax.devices()
        platform = devices[0].platform
        if platform == "cpu" and len(devices) >= n:
            return len(devices)
        if platform != "cpu":
            print(
                f"hostmesh: leaving the initialized {platform!r} backend "
                f"({len(devices)} device(s)) for {n} virtual CPU devices — "
                "nothing after this point runs on the accelerator",
                file=sys.stderr, flush=True)
        import jax.extend as jex

        jex.backend.clear_backends()
    jax.config.update("jax_num_cpu_devices", n)
    jax.config.update("jax_platforms", "cpu")
    return len(jax.devices())
