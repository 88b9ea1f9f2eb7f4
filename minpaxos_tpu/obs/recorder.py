"""Per-tick flight recorder: a fixed-size numpy ring of dispatch rows.

Every protocol-thread wakeup appends ONE row (a single slice-assign
into a preallocated int64 matrix — no allocation, no growth): when it
happened, which dispatch regime ran (full / fused / narrow /
idle-skip — PR 1's multi-modal tick cost), how many substeps fused,
rows in/out, the commit frontier, the exec backlog, and the per-phase
wall decomposition in microseconds. The ring holds the last
``capacity`` ticks; the control plane's TRACE verb exports it as
Chrome trace-event JSON that loads directly in Perfetto
(``ui.perfetto.dev``) or ``chrome://tracing`` — per-phase latency
decomposition is exactly what the "Paxos in the Cloud" experience
report says deployments live or die by, and what PERF.md's round-6
misfire hunt had to reconstruct by hand from stderr.

Schema v2 (the pipelined tick loop): the old ``step_us`` — one blocking
device-step+transfer wall — no longer exists as a single phase. The
runtime now ENQUEUES the jitted step without blocking, runs the
previous tick's host phases while the device computes, and only then
reads the outputs back, so the dispatch splits into ``enqueue_us``
(host wall to launch the async dispatch) and ``readback_us`` (host
blocked on the three stacked-array transfers). ``overlap_us`` is the
portion of THIS tick's host-phase wall (persist+dispatch+reply) that
executed while a LATER dispatch was in flight on the device — i.e.
host work the pipeline hid under device compute; 0 for a tick whose
host phases ran serially after its own readback. Consumers check
``SCHEMA_VERSION`` (carried by ``chrome_trace``) before indexing.

Timestamps are ``monotonic_ns`` (CLOCK_MONOTONIC is machine-wide on
Linux), so traces merged across the replica processes of one host
share a timeline.
"""

from __future__ import annotations

import sys
import threading
import time

import numpy as np

#: ring-row layout revision; bumped whenever fields change meaning or
#: position (v1: 12 fields with a single step_us; v2: enqueue_us /
#: readback_us / overlap_us split, 14 fields; v3: trailing
#: chaos_faults — cumulative paxchaos injected-fault count at this
#: tick, so Perfetto shows fault bursts against tick regimes; v4:
#: paxray device-round tracks — the resident loop's post-window
#: telemetry readback rendered as round slices + counter tracks under
#: the reserved DEVICE_PID, mergeable with host flight-recorder events
#: into one validated timeline. The tick-row layout itself is
#: unchanged from v3. v5: paxtrace per-command span tracks
#: (obs/trace.py) — stage slices for sampled commands under the
#: reserved TRACE_PID, so one merged file shows a command's client ->
#: replica -> device-rounds -> reply chain next to the tick and
#: device-round tracks. Tick-row layout again unchanged. v6: paxwatch
#: cluster-event tracks (obs/watch.py) — journal events (elections,
#: leader changes, failovers, chaos installs, store-corruption
#: recoveries, narrow fallbacks, alarms) rendered as instant events
#: under the reserved WATCH_PID, so one merged file shows WHEN the
#: cluster's incidents happened against the tick / device-round /
#: command-span tracks. Tick-row layout unchanged from v3. v7:
#: ingress-coalescer fields — ``coal_occ`` (client rows the
#: event-driven ingress front batched into this tick's drain) and
#: ``coal_wake`` (cumulative condition-variable kicks that woke a
#: parked tick loop), appended AFTER chaos_faults so pre-v7 field
#: indices still hold. v8: the tick loop's phases are measured by
#: ``phase`` (below) — one interval, written both into the row and,
#: while a profile is being taken, into the xplane as a
#: ``paxos.tick.*`` span on the device trace's clock — and four fields
#: are appended after coal_wake: ``wait_us`` (the blocking queue wait,
#: which earlier rows threw away), ``fsync_us`` / ``fsync_bytes`` (the
#: ``os.fsync`` inside persist_us and the bytes it made durable) and
#: ``cpu_us`` (the protocol thread's own CPU time over the row). v9:
#: where inside enqueue and egress the work happens, and every phase's
#: CPU time beside its wall — appended after cpu_us, so the 22 fields of
#: v8 keep their places: four sub-phase walls (``assemble_us`` /
#: ``call_us`` nested in enqueue_us, ``peer_send_us`` / ``flush_us``
#: nested in dispatch_us), then one ``*_cpu_us`` per phase field: the
#: protocol thread's ``thread_time_ns`` over exactly the interval the
#: wall field measures, on the rows whose ``cpu_sampled`` is 1 (the
#: thread clock is a system call, 6-52 us on the chip's host: the
#: runtime reads it per phase for one row in ``CPU_SAMPLE_EVERY``).
#: On those rows the seven tiling phases' CPU adds up to ``cpu_us``.)
SCHEMA_VERSION = 9

# dispatch regimes (runtime/replica.py classifies one per tick:
# narrow > fused > full; idle-skip never reaches the device)
KIND_FULL, KIND_FUSED, KIND_NARROW, KIND_IDLE_SKIP = 0, 1, 2, 3
KIND_NAMES = ("full", "fused", "narrow", "idle_skip")

# ring-row field layout (glossary in OBSERVABILITY.md). Two
# timestamps because a pipelined tick's phases occupy two wall-time
# intervals: the dispatch phases (drain/enqueue/readback) end at
# t_rb_ns, the host phases (persist/dispatch/reply) end at t_ns —
# with the NEXT tick's dispatch phases in between when deferred.
# Stamping only completion time would draw the dispatch phases where
# they never ran and overlap consecutive tick slices in a viewer.
(F_T_NS, F_KIND, F_K, F_ROWS_IN, F_ROWS_OUT, F_FRONTIER, F_BACKLOG,
 F_DRAIN_US, F_ENQUEUE_US, F_READBACK_US, F_OVERLAP_US, F_PERSIST_US,
 F_DISPATCH_US, F_REPLY_US, F_T_RB_NS, F_CHAOS, F_COAL_OCC,
 F_COAL_WAKE, F_WAIT_US, F_FSYNC_US, F_FSYNC_BYTES,
 F_CPU_US,
 # v9: the sub-phase walls, then a CPU field a phase (wall order)
 F_ASSEMBLE_US, F_CALL_US, F_PEER_SEND_US, F_FLUSH_US,
 F_WAIT_CPU_US, F_DRAIN_CPU_US, F_ENQUEUE_CPU_US, F_READBACK_CPU_US,
 F_PERSIST_CPU_US, F_FSYNC_CPU_US, F_DISPATCH_CPU_US, F_REPLY_CPU_US,
 F_ASSEMBLE_CPU_US, F_CALL_CPU_US, F_PEER_SEND_CPU_US,
 F_FLUSH_CPU_US, F_CPU_SAMPLED) = range(39)
N_FIELDS = 39
FIELD_NAMES = ("t_ns", "kind", "k", "rows_in", "rows_out", "frontier",
               "exec_backlog", "drain_us", "enqueue_us", "readback_us",
               "overlap_us", "persist_us", "dispatch_us", "reply_us",
               "t_rb_ns", "chaos_faults", "coal_occ", "coal_wake",
               "wait_us", "fsync_us", "fsync_bytes", "cpu_us",
               "assemble_us", "call_us", "peer_send_us", "flush_us",
               "wait_cpu_us", "drain_cpu_us", "enqueue_cpu_us",
               "readback_cpu_us", "persist_cpu_us", "fsync_cpu_us",
               "dispatch_cpu_us", "reply_cpu_us", "assemble_cpu_us",
               "call_cpu_us", "peer_send_cpu_us", "flush_cpu_us",
               "cpu_sampled")

# ---------------------------------------------------------------- phases
# The tick loop's spans (schema v8): constant names, so a reduction of
# the xplane finds them after any refactor, each feeding one wall field
# of the row and (v9) one CPU field. Seven TILE the protocol thread's
# wall; five nest, each inside the parent its name extends and included
# in that parent's field: fsync in persist, assemble and call in
# enqueue (they cover all of it but a line of glue), peers and flush in
# egress, whose SELF time (dispatch_us - peer_send_us - flush_us) is
# ``_host_catchup``. The two pod spans feed no row but a ring of the
# pod's own (parallel/sharded.py run_resident, ``process_pods()``).
#: the runtime measures the per-phase CPU times of one row in this many
#: (runtime/replica.py): a read of the thread's CPU clock is a system
#: call that took 6 us alone and 52 us beside eight busy threads on the
#: chip's host (0.5 us on the sandbox; PERF.md section 6, PR 37), and a
#: row needs nineteen
CPU_SAMPLE_EVERY = 8

PH_WAIT = "paxos.tick.wait"
PH_DRAIN = "paxos.tick.drain"
PH_ENQUEUE = "paxos.tick.enqueue"
PH_READBACK = "paxos.tick.readback"
PH_PERSIST = "paxos.tick.persist"
PH_FSYNC = "paxos.tick.fsync"
PH_EGRESS = "paxos.tick.egress"
PH_REPLY = "paxos.tick.reply"
PH_ASSEMBLE = "paxos.tick.enqueue.assemble"
PH_CALL = "paxos.tick.enqueue.call"
PH_PEERS = "paxos.tick.egress.peers"
PH_FLUSH = "paxos.tick.egress.flush"
PH_POD_DISPATCH = "paxos.pod.dispatch"
PH_POD_READBACK = "paxos.pod.readback"
PHASE_FIELDS = {PH_WAIT: F_WAIT_US, PH_DRAIN: F_DRAIN_US,
                PH_ENQUEUE: F_ENQUEUE_US, PH_READBACK: F_READBACK_US,
                PH_PERSIST: F_PERSIST_US, PH_FSYNC: F_FSYNC_US,
                PH_EGRESS: F_DISPATCH_US, PH_REPLY: F_REPLY_US,
                PH_ASSEMBLE: F_ASSEMBLE_US, PH_CALL: F_CALL_US,
                PH_PEERS: F_PEER_SEND_US, PH_FLUSH: F_FLUSH_US}
#: nested phase -> the phase it runs inside; the others tile the wall
NESTED_IN = {PH_FSYNC: PH_PERSIST, PH_ASSEMBLE: PH_ENQUEUE,
             PH_CALL: PH_ENQUEUE, PH_PEERS: PH_EGRESS, PH_FLUSH: PH_EGRESS}
TILING_PHASES = tuple(p for p in PHASE_FIELDS if p not in NESTED_IN)
#: phase -> the row field of its CPU time: the wall field's name with
#: ``_cpu`` before the unit
PHASE_CPU_FIELDS = {
    name: FIELD_NAMES.index(FIELD_NAMES[f][:-3] + "_cpu_us")
    for name, f in PHASE_FIELDS.items()}

_annotation_cls = None  # jax.profiler.TraceAnnotation, once JAX is loaded
_wall_ns = time.perf_counter_ns  # bound once: a phase reads them 3-4 times
_cpu_ns = time.thread_time_ns


def _annotate(name: str, replica: int | None):
    """A started ``jax.profiler.TraceAnnotation`` while a profile is
    being taken, else None. JAX is looked up, never imported: a process
    that has not loaded it (paxtop, tail, the smoke) cannot be taking a
    JAX profile, and obs/ stays importable without it."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if getattr(jax, "profiler", None) is None:
            return None
        _annotation_cls = jax.profiler.TraceAnnotation
    if not _annotation_cls.is_enabled():
        return None
    if replica is None:
        return _annotation_cls(name)
    return _annotation_cls(name, replica=replica)


class PhaseClock:
    """What one tick loop has spent in each phase since the row that
    phase belongs to was last cut, on two clocks: ``ns`` the wall,
    ``cpu`` the owning thread's ``thread_time_ns`` over the same
    interval (a blocked wait, a held GIL, a disk and a device burn
    none of it, so wall - cpu is what the thread spent OFF the CPU in
    that phase). Single-writer (the protocol thread).

    The outermost phases TILE the thread's wall: one that opens is
    charged from the instant the one before it closed, so the glue
    between two ``with`` blocks (counters, the burn-rate check, the
    recorder's own write: tens of microseconds) belongs to the phase
    that follows, and a wakeup that cuts no row leaves its time for
    the next row. The rows of a recorder therefore add up to the wall
    they span, exactly. A nested phase (fsync inside persist) is
    charged its own interval only.

    The CPU clock is read only while ``sample`` is set (the owner
    turns it on for the phases of the rows it samples): the read is a
    system call, dear on some hosts. A phase that runs with it off
    adds nothing to ``cpu``."""

    __slots__ = ("replica", "ns", "cpu", "sample", "_row_cpu_us",
                 "_cpu_cut", "_depth", "_last_end", "_last_cpu")

    def __init__(self, replica: int):
        self.replica = replica
        self.ns = dict.fromkeys(PHASE_FIELDS, 0)
        self.cpu = dict.fromkeys(PHASE_FIELDS, 0)
        self.sample = True
        self._row_cpu_us = 0  # tiling phases' CPU taken for the open row
        self._cpu_cut: int | None = None  # the CPU clock at the last row
        self._depth = 0
        self._last_end = 0  # when the last outermost phase closed
        # and the thread's CPU clock then (None: it was not read)
        self._last_cpu: int | None = None

    def adopt(self) -> None:
        """The calling thread owns the clock from here on: CPU time is
        counted on ITS clock, from now (a recovery that ran phases on
        the starting thread leaves their wall for the first row, not
        another thread's CPU clock)."""
        self.cpu = dict.fromkeys(PHASE_FIELDS, 0)
        self._row_cpu_us = 0
        self._last_cpu = None
        self._cpu_cut = _cpu_ns()

    def take_us(self, name: str) -> int:
        us, self.ns[name] = self.ns[name] // 1000, 0
        return us

    def take_cpu_us(self, name: str) -> int:
        us, self.cpu[name] = self.cpu[name] // 1000, 0
        if name not in NESTED_IN:
            self._row_cpu_us += us
        return us

    def cpu_us(self, sampled: bool = False) -> int:
        """The row's ``cpu_us``, at the moment it is cut: the thread's
        CPU time since the last row (0 for the first), ONE clock read;
        for a ``sampled`` row, whose phases ran with ``sample`` set,
        what ``take_cpu_us`` handed out of its TILING phases, so exactly
        the sum of the row's seven tiling ``*_cpu_us`` fields (a nested
        phase's CPU is inside its parent's). A blocked wait burns none,
        so over a row this is how much of its wall the thread ran."""
        now = _cpu_ns()
        prev, self._cpu_cut = self._cpu_cut, now
        taken, self._row_cpu_us = self._row_cpu_us, 0
        if sampled:
            return taken
        return 0 if prev is None else (now - prev) // 1000


class phase:
    """``with phase(PH_PERSIST, clock): ...`` — one measurement, two
    readers: the interval is added to ``clock`` (which the next
    recorder row drains), the wall always and the thread's CPU time
    while ``clock.sample`` is set, and, while a JAX profile is being
    taken, recorded as a TraceAnnotation of that constant name with
    ``replica=<id>``, on the device trace's clock. ``clock=None``
    annotates only. With no profile running the cost is two wall-clock
    reads and one ``is_enabled`` call, and under ``sample`` one or two
    reads of the thread's CPU clock (an outermost phase starts where
    the last one ended). ``ns`` is the block's own interval; what the
    clock is charged may start earlier (see ``PhaseClock``)."""

    __slots__ = ("name", "clock", "ns", "_t0", "_from", "_cpu_from", "_ann")

    def __init__(self, name: str, clock: PhaseClock | None = None):
        self.name = name
        self.clock = clock
        self.ns = 0  # the interval, once the block has exited

    def __enter__(self):
        # the annotation opens and closes INSIDE the measured interval:
        # what a profile costs the loop shows in the rows, not between
        self._t0 = self._from = _wall_ns()
        clock = self.clock
        if clock is not None:
            tiles = clock._depth == 0 and clock._last_end
            if tiles:
                self._from = clock._last_end
            if not clock.sample:
                self._cpu_from = None
            elif tiles and clock._last_cpu is not None:
                self._cpu_from = clock._last_cpu
            else:
                self._cpu_from = _cpu_ns()
            clock._depth += 1
        self._ann = _annotate(
            self.name, None if clock is None else clock.replica)
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        end = _wall_ns()
        self.ns = end - self._t0
        clock = self.clock
        if clock is not None:
            cpu = None
            if self._cpu_from is not None:
                cpu = _cpu_ns()
                clock.cpu[self.name] += cpu - self._cpu_from
            clock.ns[self.name] += end - self._from
            clock._depth -= 1
            if clock._depth == 0:
                clock._last_end = end
                clock._last_cpu = cpu

# dispatch-side phases, laid end-to-end ENDING at t_rb_ns (tid 0),
# and host-side phases ending at t_ns (tid 1 — their own track, so a
# deferred tick's host work rendered under the next tick's dispatch
# slice is the overlap made visible). overlap_us is in NEITHER list:
# it is an attribute of the host walls (how much was device-hidden),
# not an additional phase — it rides the tick args + a counter track.
_DISPATCH_PHASES = (("drain", F_DRAIN_US), ("enqueue", F_ENQUEUE_US),
                    ("readback", F_READBACK_US))
_HOST_PHASES = (("persist", F_PERSIST_US), ("dispatch", F_DISPATCH_US),
                ("reply", F_REPLY_US))
# nested phases, drawn inside their parent's slice: (name, field, at
# the parent's END?). assemble opens enqueue and the call closes it;
# the peer frames open egress and the flushes close it (the row holds
# egress's two halves as one sum, so what lies between peers and flush
# in the drawing is _host_catchup, its self time); fsync closes persist
_NESTED_PHASES = {
    F_ENQUEUE_US: (("assemble", F_ASSEMBLE_US, False),
                   ("call", F_CALL_US, True)),
    F_PERSIST_US: (("fsync", F_FSYNC_US, True),),
    F_DISPATCH_US: (("peers", F_PEER_SEND_US, False),
                    ("flush", F_FLUSH_US, True))}

_EVENT_PHASES = frozenset("XBEiICMsnbe")  # trace-event ph codes we accept

# ---------------------------------------------------------------- paxray
# Device-side telemetry for the resident measured loop (schema v4).
# The resident scan (parallel/sharded.py sharded_run_resident)
# accumulates ONE int32 row per protocol round in a donated device
# buffer; the host reads the buffer back exactly once after the
# measured window and renders it here as Perfetto tracks. The layout
# is canonical HERE (obs stays numpy-only, importable by paxtop with
# no JAX) and ops/telemetry.py — the jnp row constructor traced inside
# the scan — imports it, so the two sides can never drift.

#: reserved pid for device-round tracks in merged traces. Host
#: flight-recorder events use replica-id pids (small ints); the
#: validator enforces that ``device_round`` events carry exactly this
#: pid so a merged file keeps one unambiguous device track group.
#: (obs/trace.py reserves the sibling TRACE_PID = 9998 for paxtrace
#: command-span tracks; the validator pins that one too.)
DEVICE_PID = 9999

#: schema v5: reserved pid for paxtrace per-command span tracks
#: (obs/trace.py emits them; it imports this constant)
TRACE_PID = 9998

#: schema v6: reserved pid for paxwatch cluster-event tracks
#: (obs/watch.py emits them; it imports this constant). The validator
#: pins the reservation both directions, like its two siblings.
WATCH_PID = 9997

# telemetry-row field layout (glossary in OBSERVABILITY.md):
# round — absolute protocol round index (-1 = row never written);
# committed_delta — instances committed this round, summed over
#   shards at the cursor replica; in_flight — assigned-but-uncommitted
#   after the round; assigned — log slots assigned this round;
# injected_rows — live workload rows synthesized into the ext inbox;
# inbox_rows — routed peer rows delivered from the pending inboxes;
# claim_rows — rows applied through the KV claim path (executed-slot
#   delta — the per-row cost driver ROADMAP item 1 names);
# prepared_shards — shards whose cursor replica is a prepared leader
#   (== n_shards is the steady state; below it, an election/recovery
#   is in flight);
# inbox_hwm — the round's max per-(shard, replica) DELIVERED inbox
#   rows, routed + injected (inbox_rows is the routed cross-cluster
#   SUM; the per-inbox max is what a single inbox must hold). On the
#   device the same reduction over the pending rows picks each
#   round's tier (parallel/sharded.py sharded_round: rows at or
#   beyond the working capacity send the round to the full one); on
#   the host its high-water mark over a run is the occupancy a
#   configuration's inbox and small tier are sized from (PR 28).
(TEL_ROUND, TEL_COMMITTED, TEL_IN_FLIGHT, TEL_ASSIGNED, TEL_INJECTED,
 TEL_INBOX_ROWS, TEL_CLAIM_ROWS, TEL_PREPARED, TEL_INBOX_HWM) = range(9)
N_TEL_FIELDS = 9
TEL_FIELD_NAMES = ("round", "committed_delta", "in_flight", "assigned",
                   "injected_rows", "inbox_rows", "claim_rows",
                   "prepared_shards", "inbox_hwm")


def telemetry_valid_rows(buf) -> np.ndarray:
    """The written rows of a telemetry buffer readback, sorted by
    round ([n, N_TEL_FIELDS] int). Unwritten ring rows are initialized
    with round == -1 and are dropped here."""
    rows = np.asarray(buf)
    if rows.ndim != 2 or rows.shape[1] != N_TEL_FIELDS:
        raise ValueError(f"telemetry buffer must be [n, {N_TEL_FIELDS}], "
                         f"got {rows.shape}")
    rows = rows[rows[:, TEL_ROUND] >= 0]
    return rows[np.argsort(rows[:, TEL_ROUND], kind="stable")]


def device_round_events(rows, dispatches: list[dict], n_shards: int,
                        pid: int = DEVICE_PID) -> list[dict]:
    """Chrome trace events for a post-window telemetry readback.

    ``rows``: telemetry rows ([n, N_TEL_FIELDS]) — either the raw
    ring buffer or ``resident_telemetry()``'s already-clean output;
    the filter/sort applied here is idempotent, so pre-validated rows
    pass through unchanged. ``dispatches``: the host loop's per-dispatch log —
    dicts with ``t0_ns``/``t1_ns`` (monotonic_ns around the dispatch,
    the same clock the flight recorder stamps) and ``round0``/``k``
    (which rounds the dispatch ran) — device rounds have no wall
    timestamps of their own, so each dispatch's rounds are laid evenly
    across its measured wall interval. Emits one ``X`` round slice per
    telemetry row (tid 0, cat ``device_round``, named by the
    election/steady flag) plus ``device_frontier`` / ``device_in_flight``
    counter tracks — the device-side twin of ``to_events``, sharing
    its timeline so a resident dispatch and the TCP runtime merge into
    one Perfetto file.
    """
    rows = telemetry_valid_rows(rows)
    by_round = {int(r[TEL_ROUND]): r for r in rows}
    events: list[dict] = []
    frontier = 0
    for d in sorted(dispatches, key=lambda d: d["t0_ns"]):
        k = int(d["k"])
        per_us = max((int(d["t1_ns"]) - int(d["t0_ns"])) / max(k, 1) / 1e3,
                     1.0)
        for j in range(k):
            r = by_round.get(int(d["round0"]) + j)
            if r is None:
                continue  # telemetry off / ring overwrote this round
            ts = int(d["t0_ns"]) / 1e3 + j * per_us
            steady = int(r[TEL_PREPARED]) >= n_shards
            frontier += int(r[TEL_COMMITTED])
            events.append({
                "name": f"round:{'steady' if steady else 'election'}",
                "cat": "device_round", "ph": "X", "ts": ts,
                "dur": per_us, "pid": pid, "tid": 0,
                "args": {name: int(r[i])
                         for i, name in enumerate(TEL_FIELD_NAMES)}})
            t_end = ts + per_us
            events.append({"name": "device_frontier", "ph": "C",
                           "ts": t_end, "pid": pid, "tid": 0,
                           "args": {"device_frontier": frontier}})
            events.append({"name": "device_in_flight", "ph": "C",
                           "ts": t_end, "pid": pid, "tid": 0,
                           "args": {"device_in_flight":
                                    int(r[TEL_IN_FLIGHT])}})
    return events


class FlightRecorder:
    """Fixed-capacity ring buffer of per-tick rows.

    ``record`` is called by the protocol thread only; ``snapshot`` /
    ``to_events`` may be called from any thread (control plane) — the
    tiny lock only orders the one-row write against the copy, it is
    never held across anything blocking.
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"recorder capacity must be >= 1: {capacity}")
        self.capacity = capacity
        self._buf = np.zeros((capacity, N_FIELDS), np.int64)
        self.total = 0  # rows ever recorded (ring holds the last cap)
        self._lock = threading.Lock()

    def record(self, t_ns: int, kind: int, k: int, rows_in: int,
               rows_out: int, frontier: int, backlog: int, drain_us: int,
               enqueue_us: int, readback_us: int, overlap_us: int,
               persist_us: int, dispatch_us: int, reply_us: int,
               t_rb_ns: int = 0, chaos_faults: int = 0,
               coal_occ: int = 0, coal_wake: int = 0, wait_us: int = 0,
               fsync_us: int = 0, fsync_bytes: int = 0,
               cpu_us: int = 0, assemble_us: int = 0, call_us: int = 0,
               peer_send_us: int = 0, flush_us: int = 0,
               wait_cpu_us: int = 0, drain_cpu_us: int = 0,
               enqueue_cpu_us: int = 0, readback_cpu_us: int = 0,
               persist_cpu_us: int = 0, fsync_cpu_us: int = 0,
               dispatch_cpu_us: int = 0, reply_cpu_us: int = 0,
               assemble_cpu_us: int = 0, call_cpu_us: int = 0,
               peer_send_cpu_us: int = 0, flush_cpu_us: int = 0,
               cpu_sampled: int = 0) -> None:
        """``t_ns``: when the tick's host phases completed. ``t_rb_ns``:
        when its readback completed (0 = unknown; to_events then lays
        the dispatch phases contiguously before the host phases, which
        is exact for serial ticks). ``chaos_faults``: the transport's
        CUMULATIVE injected-fault total at this tick (0 when paxchaos
        was never installed — traces without chaos are unchanged).
        ``coal_occ``: client rows the ingress coalescer batched into
        this tick's drain (0 = no coalescer / no client rows).
        ``coal_wake``: the coalescer's CUMULATIVE wakeup-kick count at
        this tick (schema v7; both default 0 so pre-v7 call sites are
        unchanged). Schema v8: ``wait_us`` the blocking queue wait since
        the last row, ``fsync_us`` / ``fsync_bytes`` the ``os.fsync``
        inside ``persist_us`` and the bytes it made durable, ``cpu_us``
        the protocol thread's CPU time since the last row. Schema v9:
        ``assemble_us`` / ``call_us`` the two halves of ``enqueue_us``
        (inbox assembly and the fuse / narrow choice; the jitted call),
        ``peer_send_us`` / ``flush_us`` the peer frames and the socket
        flushes inside ``dispatch_us`` (what is left of it is
        ``_host_catchup``), and per wall field ``x_us`` the thread's
        CPU time over the same interval, ``x_cpu_us``, measured where
        ``cpu_sampled`` is 1 (else 0)."""
        with self._lock:
            self._buf[self.total % self.capacity] = (
                t_ns, kind, k, rows_in, rows_out, frontier, backlog,
                drain_us, enqueue_us, readback_us, overlap_us,
                persist_us, dispatch_us, reply_us, t_rb_ns, chaos_faults,
                coal_occ, coal_wake, wait_us, fsync_us, fsync_bytes,
                cpu_us, assemble_us, call_us, peer_send_us, flush_us,
                wait_cpu_us, drain_cpu_us, enqueue_cpu_us,
                readback_cpu_us, persist_cpu_us, fsync_cpu_us,
                dispatch_cpu_us, reply_cpu_us, assemble_cpu_us,
                call_cpu_us, peer_send_cpu_us, flush_cpu_us, cpu_sampled)
            self.total += 1

    def snapshot(self, last: int | None = None) -> np.ndarray:
        """Recorded rows oldest-first (a copy; [n, N_FIELDS] int64),
        wraparound resolved. ``last`` keeps only the newest N rows."""
        with self._lock:
            n = min(self.total, self.capacity)
            if self.total <= self.capacity:
                out = self._buf[:n].copy()
            else:
                i = self.total % self.capacity
                out = np.concatenate([self._buf[i:], self._buf[:i]])
        if last is not None and 0 <= last < len(out):
            out = out[len(out) - last:]
        return out

    def to_events(self, pid: int = 0, last: int | None = None) -> list[dict]:
        """Chrome trace events for the recorded rows, at the times the
        phases actually ran: the enclosing ``X`` tick event plus the
        drain/enqueue/readback children end at ``t_rb_ns`` on tid 0
        (the dispatch track), the persist/dispatch/reply children end
        at ``t_ns`` on tid 1 (the host-phase track) — so a deferred
        tick's host work renders UNDER the next tick's dispatch slice
        instead of producing overlapping same-track slices, and the
        pipeline's overlap is visible as exactly that. ``C`` (counter)
        events graph frontier / exec backlog / ``overlap_us``. ``pid``
        should be the replica id so merged cluster traces get one
        track group per replica."""
        events: list[dict] = []
        for r in self.snapshot(last):
            disp_dur = sum(int(r[i]) for _, i in _DISPATCH_PHASES)
            host_dur = sum(int(r[i]) for _, i in _HOST_PHASES)
            t_end = int(r[F_T_NS]) / 1e3  # trace-event ts unit: us
            t_rb = (int(r[F_T_RB_NS]) / 1e3 if r[F_T_RB_NS] > 0
                    else t_end - host_dur)  # pre-v2 rows: contiguous
            t0 = t_rb - disp_dur
            kind = KIND_NAMES[int(r[F_KIND])]
            events.append({
                "name": f"tick:{kind}", "cat": "tick", "ph": "X",
                "ts": t0, "dur": max(disp_dur, 1), "pid": pid, "tid": 0,
                "args": {"kind": kind, "k": int(r[F_K]),
                         "rows_in": int(r[F_ROWS_IN]),
                         "rows_out": int(r[F_ROWS_OUT]),
                         "frontier": int(r[F_FRONTIER]),
                         "exec_backlog": int(r[F_BACKLOG]),
                         "host_us": host_dur,
                         "overlap_us": int(r[F_OVERLAP_US]),
                         "coal_occ": int(r[F_COAL_OCC]),
                         "coal_wake": int(r[F_COAL_WAKE]),
                         "wait_us": int(r[F_WAIT_US]),
                         "fsync_bytes": int(r[F_FSYNC_BYTES]),
                         "cpu_us": int(r[F_CPU_US])}})
            if r[F_WAIT_US] > 0:
                # the blocking wait BEFORE the tick slice: an idle wait
                # is not tick cost, but it is where the wall went
                events.append({"name": "wait", "cat": "phase", "ph": "X",
                               "ts": t0 - int(r[F_WAIT_US]),
                               "dur": int(r[F_WAIT_US]),
                               "pid": pid, "tid": 0})
            if int(r[F_KIND]) != KIND_IDLE_SKIP:
                for phases, t, tid in (
                        (_DISPATCH_PHASES, t0, 0),
                        (_HOST_PHASES, t_end - host_dur, 1)):
                    for name, i in phases:
                        d = int(r[i])
                        if d > 0:
                            events.append({"name": name, "cat": "phase",
                                           "ph": "X", "ts": t, "dur": d,
                                           "pid": pid, "tid": tid})
                        for child, j, at_end in _NESTED_PHASES.get(i, ()):
                            c = min(int(r[j]), d)  # never past its parent
                            if c > 0:
                                events.append({
                                    "name": child, "cat": "phase",
                                    "ph": "X", "dur": c, "pid": pid,
                                    "tid": tid,
                                    "ts": t + d - c if at_end else t})
                        t += d
            events.append({"name": "frontier", "ph": "C", "ts": t_end,
                           "pid": pid, "tid": 0,
                           "args": {"frontier": int(r[F_FRONTIER])}})
            events.append({"name": "exec_backlog", "ph": "C", "ts": t_end,
                           "pid": pid, "tid": 0,
                           "args": {"exec_backlog": int(r[F_BACKLOG])}})
            events.append({"name": "overlap_us", "ph": "C", "ts": t_end,
                           "pid": pid, "tid": 0,
                           "args": {"overlap_us": int(r[F_OVERLAP_US])}})
            if r[F_CHAOS] > 0:
                # cumulative injected-fault counter track, emitted only
                # once chaos has fired: a fault burst shows as a step in
                # the line right where the tick regimes react to it
                events.append({"name": "chaos_faults", "ph": "C",
                               "ts": t_end, "pid": pid, "tid": 0,
                               "args": {"chaos_faults": int(r[F_CHAOS])}})
            if r[F_COAL_WAKE] > 0:
                # coalescer tracks (schema v7), emitted only once the
                # ingress front has kicked at least one wakeup: the
                # per-drain occupancy line shows batch formation doing
                # its job against the tick regimes above it
                events.append({"name": "coalesce_occupancy", "ph": "C",
                               "ts": t_end, "pid": pid, "tid": 0,
                               "args": {"coal_occ": int(r[F_COAL_OCC])}})
                events.append({"name": "coalesce_wakeups", "ph": "C",
                               "ts": t_end, "pid": pid, "tid": 0,
                               "args": {"coal_wake": int(r[F_COAL_WAKE])}})
        return events


def chrome_trace(events: list[dict]) -> dict:
    """Wrap an event list in the trace-event JSON object format. The
    paxmon schema revision rides ``otherData`` (viewers ignore it;
    ``validate_chrome_trace`` and offline consumers check it)."""
    return {"traceEvents": list(events), "displayTimeUnit": "ms",
            "otherData": {"paxmonSchemaVersion": SCHEMA_VERSION}}


def validate_chrome_trace(trace) -> list[str]:
    """Schema errors for a trace-event JSON object ([] = valid).

    Checks the contract Perfetto/chrome://tracing actually rely on:
    the JSON-object form with a ``traceEvents`` list, and per event a
    string ``name``, a known ``ph`` code, numeric ``ts``, integer
    ``pid``/``tid``, a numeric non-negative ``dur`` on complete (X)
    events, and an ``args`` object of numbers on counter (C) events —
    plus the paxmon schema revision when stamped: a trace produced by
    a different ring layout (``otherData.paxmonSchemaVersion`` !=
    SCHEMA_VERSION) fails validation instead of silently mislabeling
    phases in a viewer. Schema v4 additionally pins the reserved-pid
    contract of merged device+host traces: ``device_round`` slices
    must carry DEVICE_PID and nothing else may squat on it — a host
    event landing on the device pid (or vice versa) would interleave
    the two timelines in a viewer. Used by the tests,
    ``tools/obs_smoke.py`` and paxtop's trace dump so a malformed
    export fails loudly at the source, not in a viewer.
    """
    errs: list[str] = []
    if not isinstance(trace, dict):
        return [f"trace must be a JSON object, got {type(trace).__name__}"]
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing/non-list traceEvents"]
    other = trace.get("otherData")
    if isinstance(other, dict) and "paxmonSchemaVersion" in other:
        ver = other["paxmonSchemaVersion"]
        if ver != SCHEMA_VERSION:
            errs.append(f"paxmon schema version mismatch: trace has "
                        f"{ver!r}, this build reads {SCHEMA_VERSION}")
    for i, ev in enumerate(evs):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            errs.append(f"{where}: not an object")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errs.append(f"{where}: missing string name")
        ph = ev.get("ph")
        if not isinstance(ph, str) or ph not in _EVENT_PHASES:
            errs.append(f"{where}: bad ph {ph!r}")
            continue
        if not isinstance(ev.get("ts"), (int, float)):
            errs.append(f"{where}: non-numeric ts")
        for key in ("pid", "tid"):
            if key in ev and not isinstance(ev[key], int):
                errs.append(f"{where}: non-integer {key}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{where}: X event needs numeric dur >= 0")
        if ph == "C":
            args = ev.get("args")
            if not isinstance(args, dict) or not args or not all(
                    isinstance(v, (int, float)) for v in args.values()):
                errs.append(f"{where}: C event needs numeric args")
        is_device = (ev.get("cat") == "device_round"
                     or str(ev.get("name", "")).startswith("device_"))
        if is_device and ev.get("pid") != DEVICE_PID:
            errs.append(f"{where}: device track event must carry the "
                        f"reserved pid {DEVICE_PID}, got {ev.get('pid')!r}")
        if not is_device and ev.get("pid") == DEVICE_PID:
            errs.append(f"{where}: pid {DEVICE_PID} is reserved for "
                        f"device-round tracks")
        # schema v5: paxtrace command-span tracks live on TRACE_PID and
        # nothing else may squat there — and every span must carry its
        # trace id so a viewer selection can be joined back to spans
        is_span = ev.get("cat") == "paxtrace"
        if is_span:
            if ev.get("pid") != TRACE_PID:
                errs.append(f"{where}: paxtrace event must carry the "
                            f"reserved pid {TRACE_PID}, got "
                            f"{ev.get('pid')!r}")
            args = ev.get("args")
            if not isinstance(args, dict) or "trace_id" not in args:
                errs.append(f"{where}: paxtrace event needs "
                            f"args.trace_id")
        elif ev.get("pid") == TRACE_PID:
            errs.append(f"{where}: pid {TRACE_PID} is reserved for "
                        f"paxtrace command-span tracks")
        # schema v6: paxwatch cluster-event tracks live on WATCH_PID
        # and nothing else may squat there — instant events from the
        # journal must not interleave with replica/device/span tracks
        is_watch = ev.get("cat") == "paxwatch"
        if is_watch and ev.get("pid") != WATCH_PID:
            errs.append(f"{where}: paxwatch event must carry the "
                        f"reserved pid {WATCH_PID}, got "
                        f"{ev.get('pid')!r}")
        elif not is_watch and ev.get("pid") == WATCH_PID:
            errs.append(f"{where}: pid {WATCH_PID} is reserved for "
                        f"paxwatch cluster-event tracks")
    return errs
