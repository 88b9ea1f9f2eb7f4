"""Every module of ``tests/benchmarks`` starts with no replica in the
process's registry (``minpaxos_tpu.obs.process_collection``).

The program-span readers (``benchmarks/lib/progobs.py``) read that
registry, and tests such as ``test_manifest.py``'s "a reader with
nothing to read returns nothing" expect to find it without a loaded
replica. A worker runs one file after another in one process, so a
cluster that an EARLIER file served in this process (the in-process
rehearsals of ``test_run_cli.py`` and ``test_served_mencius_check.py``
each leave three replicas with over a hundred loaded dispatches) would
be read as this module's: which file came first would decide the
result.
"""

import pytest


@pytest.fixture(autouse=True, scope="module")
def _no_replica_left_from_an_earlier_module():
    from minpaxos_tpu import obs

    obs._PROCESS_REPLICAS.clear()
    yield
