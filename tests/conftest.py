"""Test env: force an 8-device virtual CPU mesh BEFORE jax initializes.

This is the SURVEY.md §4 strategy: distributed tests run against
``--xla_force_host_platform_device_count=8`` on CPU, replacing the
reference's "run it on K8s to find out" with a real multi-device test in CI.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from distributed_tensorflow_ibm_mnist_tpu.ops.interpret import set_interpret  # noqa: E402

# The suite drives the Pallas kernels through the interpreter ON PURPOSE;
# without this a kernel called on the CPU backend is an error (ops/interpret.py).
set_interpret(True)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip(f"need 8 virtual devices, have {len(devices)}")
    return devices


def _pools_refcount_zero(router) -> bool:
    for rep in router.replicas:
        engine = rep.engine
        pool = getattr(engine, "_pool", None)
        if not rep.alive or pool is None:
            continue
        radix = getattr(engine, "_radix", None)
        if radix is None:
            if pool.allocated != 0:
                return False
            continue
        stack = [radix.root]
        while stack:
            node = stack.pop()
            if node.ref != 0:
                return False
            stack.extend(node.children.values())
        if pool.allocated != radix.n_blocks:
            return False
    return True


@pytest.fixture(scope="session")
def pools_refcount_zero():
    """``check(router) -> bool``: every live engine's KV pool is back at
    refcount zero — any page still allocated is owned by the radix trie
    with every node at ref 0 (retained zero-ref prefixes are the cache
    working as designed), nothing a request or a handoff packet holds."""
    return _pools_refcount_zero
