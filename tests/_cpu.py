"""Importable CPU-pinning preamble for tests and ad-hoc scripts: a
virtual 8-device CPU platform, so sharding paths are exercised without
TPU hardware. ``JAX_PLATFORMS=cpu`` plus the device-count flag, both
set before jax is imported — the backend reads them once, when it
comes up."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
