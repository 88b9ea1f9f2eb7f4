"""Kept only because ``tests/benchmarks/test_manifest.py`` imports this
name; deleted by the ``benchmark`` PR that repoints it at
``minpaxos_tpu.deployments``. The benchmark is ``benchmarks/run.py``.
"""

from minpaxos_tpu.deployments import SERVER_SHAPE  # noqa: F401
