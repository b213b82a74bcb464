"""CPU self-tests of the benchmark, run by path:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They check the reference against the program's own forward pass at a
small size, the trace reduction against a recorded trace, the traffic
generator's determinism, and that a broken timed path or the float8
control comes out not correct.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four CPU devices, so that a four-chip cell's tp=4 path runs here too
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
