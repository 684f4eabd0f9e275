"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The suite runs on the CPU: sharding correctness is validated on 8 virtual
host devices, and what only a chip can show is chip_smoke.py's job. The
platform is pinned both in the environment (for child processes) and in the
live jax config (in case jax was imported before this file).
"""

import os

# keep grpc-core/absl INFO chatter (GOAWAY notices on server stop, etc.) off
# stderr: it interleaves with pytest's progress lines and corrupts them
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")
os.environ.setdefault("ABSL_MIN_LOG_LEVEL", "2")

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) == 8, "tests expect an 8-device virtual CPU mesh"
