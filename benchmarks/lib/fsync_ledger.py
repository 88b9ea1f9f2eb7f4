"""Every ``os.fsync`` of this process, counted and timed from the
benchmark's side: when it returned, which file, how many bytes the file
then held, how long it took.

The program has no counter of its own for this. The ledger is what lets
``correct`` hold a served cell to "fsync before reply": a command is
durable on a replica from the first fsync that returned with the file
at or past the end of the command's record. It costs two clock reads
and one ``fstat`` per fsync, in every run alike.
"""

from __future__ import annotations

import os
import time

import numpy as np


class FsyncLedger:
    def __init__(self):
        self.events: list[tuple[float, int, int, float]] = []
        self._real = None

    def install(self) -> None:
        self._real, os.fsync = os.fsync, self._fsync

    def remove(self) -> None:
        if self._real is not None:
            os.fsync, self._real = self._real, None

    def _fsync(self, fd) -> None:
        t0 = time.monotonic()
        self._real(fd)
        t1 = time.monotonic()
        st = os.fstat(fd if isinstance(fd, int) else fd.fileno())
        self.events.append((t1, st.st_ino, st.st_size, t1 - t0))

    def of_file(self, path: str) -> dict[str, np.ndarray]:
        """The fsyncs of one file, in order: ``t_done``, ``size`` (the
        bytes durable from then on) and ``seconds`` each took."""
        ino = os.stat(path).st_ino
        ev = np.array([e for e in self.events if e[1] == ino],
                      np.float64).reshape(-1, 4)
        return {"t_done": ev[:, 0], "size": ev[:, 2].astype(np.int64),
                "seconds": ev[:, 3]}
