"""Test harness: force an 8-device virtual CPU platform so multi-chip
sharding paths (Mesh/shard_map) are exercised without TPU pods (see
cruise_control_tpu/utils/platform.py)."""

from cruise_control_tpu import enable_persistent_compile_cache
from cruise_control_tpu.utils import force_host_cpu_devices

jax = force_host_cpu_devices(8)
jax.config.update("jax_enable_x64", False)
# Without the persistent cache every test session cold-compiles the solver
# kernels. It lands in $JAX_COMPILATION_CACHE_DIR when set, else in
# <checkout>/.jax_cache (about 39 MB of CPU entries after a full run).
enable_persistent_compile_cache()

import pytest  # noqa: E402


def _memory_map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:         # not Linux: no limit to run into here
        return 0


@pytest.fixture(autouse=True, scope="module")
def _bounded_memory_maps():
    """Every live XLA:CPU executable holds memory mappings (more with eight
    forced devices), a full session compiles thousands, and the process
    dies with a native segfault inside jax's compile path once it reaches
    vm.max_map_count (65,530 by default; the seed suite died there after
    ~290 tests). Between modules, drop the compiled programs once a good
    third of that is in use — later modules recompile what they need,
    mostly as persistent-cache hits. Never inside a module: some tests
    lean on programs an earlier test of their module compiled (a 2 s
    latency objective in test_journey_slo, jit-cache-size pins)."""
    yield
    if _memory_map_count() > 24_000:
        jax.clear_caches()
