"""Test configuration: force an 8-device virtual CPU platform so sharding
and collective paths are exercised without TPU hardware (the analogue of the
reference's in-process pserver trick, ``test_TrainerOnePass.cpp:246-251``).
Set in the environment before jax is imported, so child processes the
tests start inherit it.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
