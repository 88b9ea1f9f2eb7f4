"""Open-loop load generator for the served cells: the yardstick's own
copy of ``minpaxos_tpu/soak/swarm.py`` + ``soak/profiles.py``.

Copied so that a later PR may change the program and not the yardstick.
What differs from the original, because the metrics need it:

* it keeps EVERY request of a phase — due time, first-send time, reply
  time, reply value — where the original keeps a 65,536-entry reservoir
  of latencies (a subsample is not "the tail of all requests") and
  drops the values (the check of GETs needs them);
* command ids are unique over all workers (worker w owns
  ``[w << 27, (w + 1) << 27)``), so a log record names one request;
* all workers start a phase at one absolute ``time.monotonic()``
  instant the driver picks (CLOCK_MONOTONIC is system-wide), so the
  driver knows the window and can read counters at its edges;
* values are uniform in ``[1, 2**62)``: every PUT writes a value no
  other request writes, so a stale or invented GET reply cannot pass;
* arrivals are a Poisson process conditioned on its expected count
  (``arrival_offsets``): every seed offers the same number of requests,
  where the original's count swings by its square root;
* a generator that was itself held up (the machine stalled, the worker
  was starved) works its backlog off at ``Traffic.catchup_hz``, a rate
  the configuration sustains, instead of all at once: its own stall is not the
  cell's traffic, and a burst of stall x rate is a cell of its own.
  Every request still counts from when it was DUE, and ``behind_max_s``
  still says how late the generator ran. A server that stalls while the
  generator is on time gets its requests on time, into its sockets;
* a refused request goes again after a short backoff plus a seeded
  share of it (what was shed together does not come back together); a
  request that met silence goes again only after ``SILENCE_RETRY_S``,
  where the original re-sent every unanswered request after 2 s. Both
  wait by the seconds the worker has RUN: a worker that was held up
  does not take its own absence for the server's silence;
* a worker follows the leadership where a refusal's leader hint points
  (the original asks the master after 512 refusals in a row), and
  re-sends there only what was refused;
* no paxtrace sink.

A request's latency is (reply received - time it was DUE): a stall in
the generator or the server is charged to every request it delays.
``behind_max_s`` says how late the generator itself ran.

Workers import numpy, stdlib and the program's wire codec only — no
JAX. They are spawned, never forked: the parent holds the chip.
"""

from __future__ import annotations

import multiprocessing as mp
import selectors
import socket
import time
from dataclasses import asdict, dataclass

import numpy as np

from minpaxos_tpu.wire.codec import FrameWriter, StreamDecoder
from minpaxos_tpu.wire.messages import MsgKind, make_batch

OP_PUT, OP_GET = 1, 2  # wire.messages.Op, pinned by a test

#: consecutive arrivals share a session in blocks of 2**3, so due
#: arrivals batch into multi-row frames per session under load
SESSION_BLOCK_POW2 = 3
#: a request the server REFUSED (an ok=0 reply: it took no slot, so
#: sending it again cannot log it twice) goes again after
#: REFUSED_RETRY_S * 2**min(refusals, 3), plus a seeded share of that
#: (a shorter backoff feeds a storm: at 0.5 s a 2 s stall of the server
#: kept the window full for the rest of the run, my chip runs, PR 25)
REFUSED_RETRY_S = 2.0
BACKOFF_CAP_POW2 = 3
#: a request that met SILENCE goes again only after this long: the
#: program sheds rows past its inbox's room without a reply, so silence
#: has to be retried, but it forgets a command the moment it replies, so
#: a retry that crosses a slow reply is logged twice. The timeout stands
#: far beyond any reply's time, stalled runs included
SILENCE_RETRY_S = 20.0
#: worker w's command ids start here (int32 on the wire: 16 workers)
WORKER_ID_SHIFT = 27


# ------------------------------------------------- traffic arithmetic

@dataclass(frozen=True)
class Traffic:
    """One traffic mix, as a cell's data file states it."""

    rate_hz: float
    key_range: int
    write_pct: int = 50
    zipf_s: float = 0.0
    burst_x: float = 1.0
    burst_t0_frac: float = 0.4
    burst_t1_frac: float = 0.6
    #: the most a generator that fell behind may offer while it catches
    #: up, all workers together; 0 = no faster than the cell's own rate
    catchup_hz: float = 0.0

    def to_dict(self) -> dict:
        return asdict(self)


def zipf_cdf(n_keys: int, s: float) -> np.ndarray:
    """Exact finite-support Zipf(s) CDF over ranks 1..n_keys."""
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(s)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return cdf


#: Zipfian ranks are scattered over the key space, as YCSB scatters
#: them by a hash, so that the hot keys are not neighbours; here by a
#: multiplication that is a
#: bijection of the key space (the multiplier is reduced until it shares
#: no factor with the range)
_SCRAMBLE = 2654435761


def scramble_keys(ranks: np.ndarray, key_range: int) -> np.ndarray:
    mult = _SCRAMBLE % key_range
    while np.gcd(mult, key_range) != 1:
        mult += 1
    return (ranks * mult + 0x5BD1) % key_range


def request_rows(t: Traffic, n: int, seed: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``n`` requests ``(ops, keys, vals)`` from the seed (one PCG64
    stream, fixed draw order: keys, ops, values)."""
    rng = np.random.default_rng(seed)
    if t.zipf_s > 0:
        ranks = np.searchsorted(zipf_cdf(t.key_range, t.zipf_s),
                                rng.random(n), side="right").astype(np.int64)
        keys = scramble_keys(ranks, t.key_range)
    else:
        keys = rng.integers(0, t.key_range, n).astype(np.int64)
    ops = np.where(rng.integers(0, 100, n) < t.write_pct,
                   OP_PUT, OP_GET).astype(np.int64)
    vals = rng.integers(1, 1 << 62, n).astype(np.int64)
    return ops, keys, vals


def arrival_offsets(t: Traffic, rate_hz: float, duration_s: float,
                    seed: int) -> np.ndarray:
    """Seeded arrival offsets (sorted seconds in ``[0, duration_s)``) of
    a Poisson process at ``rate_hz``, times ``burst_x`` inside the burst
    window, conditioned on its expected count: given their number, a
    Poisson process's arrivals are independent draws from its
    normalised intensity, so the gaps are Poisson's while every seed
    offers exactly the same number of requests (the seed orders the
    work, it does not size it)."""
    if rate_hz <= 0 or duration_s <= 0:
        return np.empty(0, np.float64)
    burst = t.burst_x > 1.0 and t.burst_t1_frac > t.burst_t0_frac
    # cumulative intensity at the breakpoints, in expected arrivals
    frac = [0.0, t.burst_t0_frac, t.burst_t1_frac, 1.0] if burst else [0.0, 1.0]
    mult = [1.0, t.burst_x, 1.0] if burst else [1.0]
    edges = np.asarray(frac) * duration_s
    mass = np.concatenate([[0.0], np.cumsum(
        np.diff(edges) * np.asarray(mult) * rate_hz)])
    n = int(round(mass[-1]))
    u = np.sort(np.random.default_rng(seed).random(n)) * mass[-1]
    return np.minimum(np.interp(u, mass, edges),
                      np.nextafter(duration_s, 0.0))


#: one turn of a worker's loop lasts 50 ms at the most; a longer gap
#: between two turns is time in which the process did not run
MAX_TURN_S = 0.1


def schedule_clock(sched: float, last: float, now: float,
                   catchup_x: float) -> float:
    """The instant up to which arrivals are due to be SENT: real time,
    except that after a stall of the generator (a gap between two turns
    of its loop in which it did not run) it resumes where it stopped and
    runs at most ``catchup_x`` times as fast until it has caught up, so
    that the offered rate never passes ``catchup_x`` times the cell's."""
    return min(now, sched + catchup_x * min(now - last, MAX_TURN_S))


# ------------------------------------------------------------- worker

class _Worker:
    """One worker's engine: its sessions' blocking sockets, one
    selectors loop and the open-loop injector."""

    def __init__(self, worker_id: int, maddr: tuple[str, int],
                 sessions: int):
        from minpaxos_tpu.runtime.master import get_leader, get_replica_list

        self.worker_id, self.sessions = worker_id, sessions
        self.nodes = get_replica_list(maddr)
        self.sel = selectors.DefaultSelector()
        self.opened: list[socket.socket] = []  # every socket, to close
        self.failovers = 0
        self._connect(get_leader(maddr))
        self.next_cmd = worker_id << WORKER_ID_SHIFT  # never reused

    def _connect(self, leader: int) -> None:
        """A session each to ``leader``, which requests go to from now
        on. Sockets to an earlier leader stay open to be read: a reply
        to what it took is still owed on them."""
        host, port = self.nodes[leader]
        self.leader = leader
        self.socks: list[socket.socket] = []
        self.writers: list[FrameWriter] = []
        for _ in range(self.sessions):
            sock = socket.create_connection((host, port), timeout=10.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.sendall(bytes([int(MsgKind.HANDSHAKE_CLIENT)]))
            self.sel.register(sock, selectors.EVENT_READ, StreamDecoder())
            self.socks.append(sock)
            self.writers.append(FrameWriter(sock))
        self.opened += self.socks

    def _book(self, ops, keys, vals, seed: int) -> dict:
        """One phase's requests and what became of each."""
        n = len(ops)
        nan = np.full(n, np.nan)
        return {"base": self.next_cmd, "n": n, "ops": ops, "keys": keys,
                "vals": vals, "t_sent": nan.copy(), "t_reply": nan.copy(),
                "t_retry": np.zeros(n), "reply_val": np.zeros(n, np.int64),
                "refusals": np.zeros(n, np.int64),
                "jitter": np.random.default_rng(seed ^ 0x71773).random(n),
                "rejects": 0, "duplicates": 0, "retransmits": 0}

    def _send(self, sid: int, cmds: np.ndarray, book: dict) -> None:
        k = cmds - book["base"]
        frame = make_batch(MsgKind.PROPOSE, cmd_id=cmds.astype(np.int32),
                           op=book["ops"][k], key=book["keys"][k],
                           val=book["vals"][k],
                           timestamp=time.monotonic_ns())
        self.writers[sid].write(MsgKind.PROPOSE, frame)
        self.writers[sid].flush()

    def _flush(self, cmds: np.ndarray, book: dict, ran: float) -> None:
        """One PROPOSE frame per home session for the given commands;
        each goes again at ``ran + SILENCE_RETRY_S`` unless answered."""
        sids = (cmds >> SESSION_BLOCK_POW2) % len(self.socks)
        for sid in np.unique(sids):
            self._send(int(sid), cmds[sids == sid], book)
        book["t_retry"][cmds - book["base"]] = ran + SILENCE_RETRY_S

    def _drain_events(self, events, book: dict, ran: float) -> None:
        now = time.monotonic()
        base, n = book["base"], book["n"]
        moved_to = self.leader
        for key, _ in events:
            sock, dec = key.fileobj, key.data
            chunk = sock.recv(1 << 16)
            if not chunk:
                if sock in self.socks:
                    raise OSError("a session was closed by the server")
                self.sel.unregister(sock)  # an earlier leader hung up
                continue
            for kind, rows in dec.feed(chunk):
                if kind != MsgKind.PROPOSE_REPLY:
                    continue
                k = rows["cmd_id"].astype(np.int64) - base
                mine = (k >= 0) & (k < n)
                k, ok, val = k[mine], rows["ok"][mine], rows["val"][mine]
                rej = ok == 0
                book["rejects"] += int(rej.sum())
                # a refusal names the leader the replica knows of
                elsewhere = rows["leader"][mine][rej]
                elsewhere = elsewhere[(elsewhere >= 0)
                                      & (elsewhere != self.leader)]
                if len(elsewhere) and sock in self.socks:
                    moved_to = int(elsewhere[-1])
                backoff = REFUSED_RETRY_S * (1 << np.minimum(
                    book["refusals"][k[rej]], BACKOFF_CAP_POW2))
                book["t_retry"][k[rej]] = ran + backoff * (
                    1.0 + 0.5 * book["jitter"][k[rej]])
                book["refusals"][k[rej]] += 1
                k, val = k[~rej], val[~rej]
                first = np.isnan(book["t_reply"][k])
                book["duplicates"] += int((~first).sum())
                book["t_reply"][k[first]] = now
                book["reply_val"][k[first]] = val[first]
        if moved_to != self.leader:
            # the leadership moved: what was REFUSED goes to the new
            # leader when its backoff is up; what met silence waits its
            # timeout out as ever (the old leader may have taken it)
            self._connect(moved_to)
            self.failovers += 1

    def _retransmit(self, ran: float, book: dict) -> None:
        due = np.nonzero(~np.isnan(book["t_sent"])
                         & np.isnan(book["t_reply"])
                         & (ran >= book["t_retry"]))[0]
        if len(due):
            book["retransmits"] += len(due)
            self._flush(due + book["base"], book, ran)

    def run_phase(self, traffic: dict, rate_hz: float, duration_s: float,
                  seed: int, t0: float, drain_timeout_s: float) -> dict:
        """Inject this worker's share of one phase starting at the
        absolute monotonic instant ``t0``, then keep serving replies
        until nothing is outstanding or ``drain_timeout_s`` has passed
        since the phase's close. Returns every request of the phase."""
        t = Traffic(**traffic)
        catchup_x = max(t.catchup_hz / t.rate_hz, 1.0)
        offs = arrival_offsets(t, rate_hz, duration_s, seed)
        n = len(offs)
        ops, keys, vals = request_rows(t, n, seed ^ 0x9E3779B9)
        book = self._book(ops, keys, vals, seed)
        self.next_cmd += n
        due = t0 + offs
        end = t0 + duration_s
        send_i, behind_max, sent_by = 0, 0.0, end
        sched = last = min(time.monotonic(), t0)
        ran = 0.0  # seconds this loop has run: retransmission's clock
        while True:
            now = time.monotonic()
            ran += min(now - last, MAX_TURN_S)
            sched, last = schedule_clock(sched, last, now, catchup_x), now
            if send_i >= n and (now >= sent_by + drain_timeout_s or (
                    now >= end and not np.isnan(book["t_reply"]).any())):
                break
            if send_i < n and due[send_i] <= sched:
                # everything the schedule's clock has reached goes now,
                # one frame per home session: offered load is conserved
                j = int(np.searchsorted(due, sched, side="right"))
                behind_max = max(behind_max, now - due[send_i])
                book["t_sent"][send_i:j] = now
                self._flush(np.arange(send_i, j) + book["base"], book, ran)
                send_i, sent_by = j, max(end, now)
            nxt = due[send_i] if send_i < n else now + 0.05
            if sched < now - 1e-3:  # catching up: step the clock, no spin
                nxt = now + 0.002
            wait = min(0.05, max(nxt - time.monotonic(), 0.0))
            self._drain_events(self.sel.select(timeout=wait), book, ran)
            self._retransmit(ran, book)
        return {"worker": self.worker_id, "n": n,
                "cmd_id": np.arange(n, dtype=np.int64) + book["base"],
                "op": ops, "key": keys, "val": vals, "t_due": due,
                "t_sent": book["t_sent"], "t_reply": book["t_reply"],
                "reply_val": book["reply_val"],
                "rejects": book["rejects"],
                "duplicates": book["duplicates"],
                "retransmits": book["retransmits"],
                "failovers": self.failovers,
                "behind_max_s": behind_max}

    def close(self) -> None:
        for sock in self.opened:
            try:
                sock.close()
            except OSError:
                pass
        self.sel.close()


def _worker_main(conn, cfg: dict) -> None:
    """Spawn target. The parent sends ``("phase", traffic, rate_hz,
    duration_s, seed, t0, drain_timeout_s)`` or ``("stop",)``; the
    worker answers each with one dict (the first is the connect ack).
    Any failure travels back as ``{"error": ...}``."""
    try:
        worker = _Worker(cfg["worker_id"], tuple(cfg["maddr"]),
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


# ------------------------------------------------------------- driver

class OpenLoopLoad:
    """Driver-side handle: ``workers`` spawned processes, each with
    ``sessions / workers`` TCP sessions to the leader."""

    def __init__(self, maddr: tuple[str, int], sessions: int, workers: int):
        if sessions % workers or workers > 16:
            raise ValueError(f"sessions ({sessions}) must divide evenly "
                             f"into at most 16 workers ({workers})")
        self.maddr, self.sessions, self.workers = maddr, sessions, workers
        self._procs: list = []
        self._pipes: list = []

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

    def _collect(self, timeout_s: float) -> list[dict]:
        out = []
        deadline = time.monotonic() + timeout_s
        for w, pipe in enumerate(self._pipes):
            if not pipe.poll(max(deadline - time.monotonic(), 0.0)):
                raise TimeoutError(f"load worker {w} did not answer")
            out.append(pipe.recv())
        return out

    def begin_phase(self, traffic: Traffic, duration_s: float, seed: int,
                    drain_timeout_s: float, lead_s: float = 0.3) -> float:
        """Start one phase on every worker at one instant; returns it.
        Each worker runs ``rate_hz / workers`` from its own seed, so the
        schedule is a pure function of (seed, workers)."""
        t0 = time.monotonic() + lead_s
        for w, pipe in enumerate(self._pipes):
            pipe.send(("phase", traffic.to_dict(),
                       traffic.rate_hz / self.workers, duration_s,
                       seed * 131 + w, t0, drain_timeout_s))
        self._deadline = duration_s + drain_timeout_s + lead_s + 30.0
        return t0

    def end_phase(self) -> dict:
        """Wait for every worker's phase (drain included) and merge:
        one row per request, in no particular order."""
        res = self._collect(self._deadline)
        bad = [r for r in res if "error" in r]
        if bad:
            raise RuntimeError(f"load worker failed: {bad[0]['error']}")
        out = {k: np.concatenate([r[k] for r in res])
               for k in ("cmd_id", "op", "key", "val", "t_due", "t_sent",
                         "t_reply", "reply_val")}
        for k in ("rejects", "duplicates", "retransmits", "failovers"):
            out[k] = sum(r[k] for r in res)
        out["behind_max_s"] = max(r["behind_max_s"] for r in res)
        return out

    def stop(self) -> None:
        """Stop every worker and wait until each has ended."""
        for pipe in self._pipes:
            try:
                pipe.send(("stop",))
            except OSError:
                pass
        for p in self._procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=10.0)
        self._procs, self._pipes = [], []
