"""Test configuration: force CPU with 8 virtual devices BEFORE jax imports.

This is the moral equivalent of the reference's DummyBackend test seam
(`/root/reference/dalle_pytorch/distributed_backends/dummy_backend.py`) —
except our fake 8-device mesh actually exercises the real sharding and
collective code paths.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
