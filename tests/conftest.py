"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The environment variables must be set before jax is imported anywhere
(pytest imports conftest first). Tests use the CPU for determinism and
to exercise the multi-chip sharding paths; the chip is reached only
through chip_smoke.py.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache: the verify kernel is a large program
# (SHA-512 + curve math in one jit); caching makes reruns start fast.
from tendermint_tpu.libs import jaxcache

jaxcache.configure()
