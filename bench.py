"""Kept only because ``tests/benchmarks/test_manifest.py`` and
``tests/benchmarks/test_mencius_cell.py`` import this name; deleted by
the ``benchmark`` PR that repoints them at
``minpaxos_tpu.deployments``. The benchmark is ``benchmarks/run.py``.
"""

from minpaxos_tpu.deployments import headline_config, side_shapes  # noqa: F401
