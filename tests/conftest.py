"""Test bootstrap: an 8-device virtual CPU platform, so multi-chip sharding
paths are exercised without TPU hardware.  Both variables are read when
jax is first imported, which is after this file."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
