"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Tests check correctness and counts, on the CPU only; sharding is
validated on 8 virtual CPU devices (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip). What runs on the
chip is checked by chip_smoke.py, through the chip tool.
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

# the suite runs on the virtual 8-device CPU mesh whatever the machine
# holds: pin the platform before any backend init, so a chip, where
# there is one, is never claimed by a test process
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# shared persistent compile cache (JAX_COMPILATION_CACHE_DIR, else the
# repo-local .jax_cache): the suite boots many real server processes
# that would otherwise each re-jit identical kernels for seconds
from minpaxos_tpu.utils.backend import enable_compile_cache  # noqa: E402

enable_compile_cache()


import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow')")


#: XLA:CPU maps a few memory regions per compiled program, and the jit
#: caches keep every program alive: a full tier-1 run crossed the
#: kernel's per-process limit (vm.max_map_count, 65,530) around
#: test_sharded — the next compile failed ("LLVM compilation error:
#: Cannot allocate memory"), the interpreter segfaulted and the last
#: ~80 tests never ran. Past this many maps, a module's teardown drops
#: the caches (the maps go with them; later modules reload what they
#: share from the persistent compile cache).
MAPS_BEFORE_CLEAR = 24_000


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count, nothing to do
        return
    if n_maps > MAPS_BEFORE_CLEAR:
        jax.clear_caches()
