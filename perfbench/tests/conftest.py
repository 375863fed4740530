"""The benchmark's own tests run on the CPU with four virtual devices, so
that the zero1 exchange between members exists.  Run them from the root of
the checkout:

    python -m pytest perfbench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
sys.path[:0] = [PERFBENCH, os.path.join(os.path.dirname(PERFBENCH), "src")]
