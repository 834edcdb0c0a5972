"""Test configuration: force an 8-device virtual CPU mesh before JAX use.

The chip is exercised by ``chip_smoke.py``; the test suite runs on a
virtual 8-device CPU platform so sharding paths (pjit over a Mesh) are
testable without multi-chip hardware, and ``tests/test_chip_compile.py``
compiles the kernels for a described v5e.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache (utils/compile_cache.py): the crypto kernels
# are compile-heavy; caching cuts repeat suite runs from tens of minutes to
# minutes.  Shared with the entry points.
from quantum_resistant_p2p_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402

#: XLA's CPU client maps memory for every compiled program it keeps; a
#: worker that keeps every program of every module alive crosses the
#: kernel's vm.max_map_count (65530) and segfaults inside a later compile
#: (test_fused + test_mldsa alone reach ~50k maps)
_MAPS_HIGH_WATER = 20000


def _maps_in_use() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to guard
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop this module's compiled programs once the process holds many
    maps; the persistent compile cache reloads any a later module needs."""
    yield
    if _maps_in_use() > _MAPS_HIGH_WATER:
        jax.clear_caches()
