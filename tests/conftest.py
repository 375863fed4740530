import os
import sys

# NOTE: no XLA_FLAGS here on purpose — unit/smoke tests run on the single
# real CPU device.  Multi-device tests (tests/test_distributed.py) spawn
# subprocesses with their own --xla_force_host_platform_device_count.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the entry points turn JAX's persistent compile cache on (repro.launch.
# compile_cache); tests and the subprocesses they start inherit this and
# keep no cache in the checkout
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402  (sys.path bootstrap must precede)

jax.config.update("jax_enable_x64", False)
jax.config.update("jax_enable_compilation_cache", False)
