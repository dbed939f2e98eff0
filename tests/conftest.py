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
