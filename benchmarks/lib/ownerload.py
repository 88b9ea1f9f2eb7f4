"""``lib/loadgen.py``'s open-loop load with its sessions SPREAD over
the replicas instead of gathered at the leader: the upstream client's
``-e`` mode, for a cluster in which every replica proposes (Mencius).

Session ``i`` of the run (worker ``w``'s ``j``-th is ``w * sessions per
worker + j``) connects to replica ``i mod N`` and stays there: a
request is sent, and retransmitted, on its home session, so on its
session's owner. The schedule, Poisson arrivals, Zipf ranks, command
ids, retransmission timer and catch-up rule are ``loadgen``'s own code;
only where a session connects differs. A refusal's leader hint moves
nothing (there is no leader to follow); ``failovers`` counts the hints.
"""

from __future__ import annotations

import multiprocessing as mp
import selectors
import socket

from benchmarks.lib import loadgen
from minpaxos_tpu.wire.codec import FrameWriter, StreamDecoder
from minpaxos_tpu.wire.messages import MsgKind


def owner_of_session(session: int, n_replicas: int) -> int:
    return session % n_replicas


def sessions_per_owner(sessions: int, n_replicas: int) -> list[int]:
    """How many of the run's sessions each replica serves."""
    return [len(range(r, sessions, n_replicas)) for r in range(n_replicas)]


class _OwnerWorker(loadgen._Worker):
    """One worker's sessions, each on its own owner."""

    def __init__(self, worker_id: int, maddr: tuple[str, int],
                 sessions: int):
        from minpaxos_tpu.runtime.master import get_replica_list

        self.worker_id, self.sessions = worker_id, sessions
        self.nodes = get_replica_list(maddr)
        self.sel = selectors.DefaultSelector()
        self.opened: list[socket.socket] = []
        self.socks: list[socket.socket] = []
        self.writers: list[FrameWriter] = []
        self.failovers = 0
        self.leader = -1  # none: a hint is counted and not followed
        for j in range(sessions):
            owner = owner_of_session(worker_id * sessions + j,
                                     len(self.nodes))
            sock = socket.create_connection(tuple(self.nodes[owner]),
                                            timeout=10.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.sendall(bytes([int(MsgKind.HANDSHAKE_CLIENT)]))
            self.sel.register(sock, selectors.EVENT_READ, StreamDecoder())
            self.socks.append(sock)
            self.writers.append(FrameWriter(sock))
        self.opened += self.socks
        self.next_cmd = worker_id << loadgen.WORKER_ID_SHIFT

    def _connect(self, leader: int) -> None:
        """The placement is fixed for the run: remember the hint so
        that it is counted once, and stay."""
        self.leader = leader


def _worker_main(conn, cfg: dict) -> None:
    """``loadgen._worker_main`` around an ``_OwnerWorker``."""
    try:
        worker = _OwnerWorker(cfg["worker_id"], tuple(cfg["maddr"]),
                              cfg["sessions"])
    except Exception as e:  # boot failure must reach the parent
        conn.send({"error": repr(e)[:300]})
        return
    conn.send({"ok": True})
    try:
        while True:
            msg = conn.recv()
            if msg[0] == "stop":
                conn.send({"ok": True})
                return
            try:
                conn.send(worker.run_phase(*msg[1:]))
            except Exception as e:  # the pipe is the error channel
                conn.send({"error": repr(e)[:300]})
    finally:
        worker.close()


class OwnerSpreadLoad(loadgen.OpenLoopLoad):
    """``OpenLoopLoad`` whose workers are ``_OwnerWorker``s."""

    def start(self, timeout_s: float = 60.0) -> None:
        ctx = mp.get_context("spawn")  # workers must not inherit JAX
        for w in range(self.workers):
            parent, child = ctx.Pipe()
            cfg = {"worker_id": w, "maddr": list(self.maddr),
                   "sessions": self.sessions // self.workers}
            p = ctx.Process(target=_worker_main, args=(child, cfg),
                            daemon=True)
            p.start()
            child.close()
            self._procs.append(p)
            self._pipes.append(parent)
        for w, reply in enumerate(self._collect(timeout_s)):
            if "error" in reply:
                raise RuntimeError(f"worker {w} failed to start: "
                                   f"{reply['error']}")
