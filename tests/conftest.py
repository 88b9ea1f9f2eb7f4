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


@pytest.fixture(autouse=True)
def _one_mencius_cell_run_at_a_time(request):
    """tests/benchmarks runs the Mencius cell from two files: in this
    process (test_mencius_cell.py) and as a subprocess (test_run_cli.py),
    whose test then asserts that no scratch directory of the cell is
    left under ``.bench_scratch`` — which the OTHER file's run, live in
    another xdist worker at that moment, breaks (ROADMAP C-m; it had
    come to fail in every whole run of PR 31's tree). A file lock
    across either keeps them apart on any machine; both files lie under
    the benchmark's ``paths`` and are not this repo's to edit."""
    nodeid = request.node.nodeid
    if not (nodeid.startswith("tests/benchmarks/") and "mencius" in nodeid):
        yield
        return
    import fcntl
    import pathlib

    scratch = pathlib.Path(__file__).resolve().parent.parent / ".bench_scratch"
    scratch.mkdir(exist_ok=True)
    with open(scratch / "lock.mencius_cell", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        yield
