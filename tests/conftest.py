"""Test environment: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of emulating multi-node on one host
(test/python/dist_test_utils.py); here 8 virtual XLA CPU devices stand in
for a TPU slice.  Must run before the first jax import.
"""
import os
import sys

# Tests always run on the virtual CPU mesh, whatever the machine holds:
# the chip belongs to one process at a time and is for chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
