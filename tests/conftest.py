"""Test fixture: CPU backend with 8 virtual devices + float64.

This is the TPU-rebuild equivalent of the reference's `sparkTest` local-mode
fixture (reference: photon-test-utils/.../test/SparkTestUtils.scala:31-77):
all distributed code paths run on an 8-device virtual CPU mesh, and parity
math runs in float64 to match the all-double JVM reference.
"""
import gc
import os
import sys

# CPU with 8 virtual devices: tier-1 runs in a sandbox with no accelerator
# (the chip is exercised by chip_smoke.py, one process per chip).  The env
# vars are what child processes started by tests inherit; jax.config covers
# this process even when a pytest plugin imported jax before this conftest
# (no backend is initialized yet at collection time).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
# Persistent compilation cache, placed by the product's own rule
# ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); only programs
# worth the disk round trip are kept.
from photon_ml_tpu.utils.jax_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(7)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """After each test module, drop every compiled program the process
    holds.  Each loaded XLA:CPU executable maps memory (~20 mappings per
    small program) and JAX's jit caches plus the package's `lru_cache`d
    solver factories keep all of them loaded for the life of the process;
    one pytest process running the whole suite otherwise walks past
    vm.max_map_count (65,530) and the next compile's mmap fails inside the
    JIT (SIGSEGV in backend_compile_and_load).  Clearing per module keeps
    within-module trace-count assertions intact."""
    yield
    for name, module in list(sys.modules.items()):
        if name.startswith("photon_ml_tpu"):
            for obj in list(vars(module).values()):
                if hasattr(obj, "cache_clear") and hasattr(obj, "cache_info"):
                    obj.cache_clear()
    jax.clear_caches()
    gc.collect()
