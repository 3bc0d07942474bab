"""Test harness config: hermetic 8-device virtual CPU mesh.

Tests run on the CPU backend: sharding correctness is validated on an
8-device host-platform mesh, and Pallas kernels run through the
interpreter (ops/gf2kernels._interpret derives it from the backend).
The chip is exercised by ``chip_smoke.py``, never by pytest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
