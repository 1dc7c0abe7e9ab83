"""Test harness config: run JAX on CPU with 8 virtual devices so sharding
tests exercise the multi-chip code paths without TPU hardware (same strategy
the driver uses for dryrun_multichip). Set before anything imports jax.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
