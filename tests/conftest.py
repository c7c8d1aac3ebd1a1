"""Test bootstrap: CPU JAX with a virtual 8-device mesh.

Multi-chip hardware is not available in CI; sharding correctness is validated
on a host-platform device mesh (SURVEY.md section 7 / driver contract).
``JAX_PLATFORMS`` is the whole platform-selection mechanism, set here before
the first jax import so the suite never takes a chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
