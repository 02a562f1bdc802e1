"""Test configuration: a virtual 8-device CPU mesh, set before JAX loads.

Tests run on the CPU backend; sharding tests run on
``xla_force_host_platform_device_count=8`` per the project test strategy.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from stateright_tpu.utils.compile_cache import (  # noqa: E402
    enable_persistent_cache,
)

enable_persistent_cache()
