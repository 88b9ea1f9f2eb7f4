"""The replica server process: one protocol thread, one jitted step.

Counterpart of the reference's server binary + genericsmr runtime +
bareminpaxos event loop (server.go:36-117, genericsmr.go:70-111,
bareminpaxos.go:247-381), restructured TPU-first: instead of a
goroutine per connection feeding per-message channels into a
select loop, reader threads enqueue decoded frames; the protocol
thread drains them into a fixed-shape column batch once per tick and
advances the WHOLE replica with one ``replica_step`` call; the outbox
scatters back to peer/client sockets. Durability, beacons, READ
serving, beyond-window catch-up, and control RPCs ride the host path
around the device step (SURVEY.md section 7.4: ragged/cold paths stay
off the device).

Single-owner: protocol state, writers, and the stable store are
touched only by the protocol thread — the reference's benign races
(SURVEY.md section 5) are structurally impossible.
"""

from __future__ import annotations

import functools
import heapq
import json
import queue
import socket
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from minpaxos_tpu.chaos import ChaosShim, FaultPlan
from minpaxos_tpu.models.minpaxos import (
    ACCEPTED,
    COMMITTED,
    NO_BALLOT,
    MinPaxosConfig,
    MsgBatch,
    become_leader,
    init_replica,
    replica_step_impl,
)
from minpaxos_tpu.obs.metrics import MetricsRegistry, TICK_MS_BUCKETS
from minpaxos_tpu.obs import register_replica
from minpaxos_tpu.obs.recorder import (
    KIND_FULL,
    KIND_FUSED,
    KIND_IDLE_SKIP,
    KIND_NARROW,
    CPU_SAMPLE_EVERY,
    PH_ASSEMBLE,
    PH_CALL,
    PH_DRAIN,
    PH_EGRESS,
    PH_ENQUEUE,
    PH_FLUSH,
    PH_FSYNC,
    PH_PEERS,
    PH_PERSIST,
    PH_READBACK,
    PH_REPLY,
    PH_WAIT,
    FlightRecorder,
    PhaseClock,
    phase,
)
from minpaxos_tpu.obs.trace import (
    ST_COMMIT,
    ST_DRAIN,
    ST_EXEC,
    ST_ORIGIN,
    ST_OWN_COMMIT,
    ST_REPLY_SER,
    TraceSink,
    protocol_ring_capacity,
    trace_id_for,
)
from minpaxos_tpu.obs.watch import (
    DET_BURN,
    EV_ALARM,
    EV_ALARM_CLEAR,
    EV_CHAOS_CLEAR,
    EV_CHAOS_INSTALL,
    EV_ELECTION,
    EV_FATAL,
    EV_LEADER_CHANGE,
    EV_NARROW_FALLBACK,
    EV_PHASE,
    EV_RECOVERY,
    EV_SNAPSHOT,
    EV_STORE_CORRUPT,
    EV_TRUNCATE,
    EventJournal,
    burn_alarm,
    event_chrome_events,
)
from minpaxos_tpu.ops.kvstore import LIVE, kv_insert_unique
from minpaxos_tpu.ops.packed import join_i64, split_i64
from minpaxos_tpu.ops.substeps import (
    SCAL_NAMES,
    SCAL_CRT_INST,
    SCAL_EXEC_COUNT,
    SCAL_EXEC_LO,
    SCAL_EXECUTED,
    SCAL_FRONTIER,
    SCAL_HIGH_ANCHOR,
    SCAL_KV_DROPPED,
    SCAL_LEADER,
    SCAL_LOW_ANCHOR,
    SCAL_PREPARED,
    SCAL_WINDOW_BASE,
    SCAL_WORK_PENDING,
    merge_view,
    narrow_view,
    scan_ticks,
)
from minpaxos_tpu.runtime import batches
from minpaxos_tpu.runtime.stable import StableStore
from minpaxos_tpu.runtime.transport import (
    CONN_LOST,
    FROM_CLIENT,
    FROM_PEER,
    Transport,
)
from minpaxos_tpu.utils.clock import cputicks, monotonic_ns
from minpaxos_tpu.utils.dlog import DLOG, dlog
from minpaxos_tpu.utils.netutil import CONTROL_OFFSET
from minpaxos_tpu.wire.messages import MsgKind, Op, empty_batch, make_batch

CONTROL = 3  # queue item source tag (transport uses 0..2)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5), donate_argnums=1)
def _packed_step(cfg, state, inbox, step_impl, k=1, narrow=0, off=0):
    """k protocol substeps + device-side packing of everything the
    host reads per dispatch into THREE stacked arrays: the per-tick
    host cost used to be ~30 per-column/per-scalar ``np.asarray``
    device reads (~1 s of the leader's CPU over a 50k-op run); one
    [k, 14, M] outbox stack, one [k, 6, E] exec stack and one
    [k, N_SCAL] scalar matrix make it
    three transfers for ALL k substeps (ops/substeps.py). Module-level
    jit: every replica in the process shares one compile cache (see
    ReplicaServer.step note).

    ``k`` (static): fused substeps per dispatch — the real inbox feeds
    substep 0, the rest run with empty inboxes, amortizing the
    0.3-0.9 ms dispatch floor over the follow-up ticks a bursty batch
    was going to need anyway. ``narrow``/``off`` (static width, traced
    offset): run the substeps on a ``narrow``-slot resident view of
    the window at offset ``off`` — the small-window specialized step;
    the host only selects it when every slot the step could touch fits
    the view (see _choose_narrow).
    """
    if narrow:
        ncfg = cfg._replace(window=narrow, slide_window=False)
        view, fields = narrow_view(state, off, narrow, cfg.window)
        view, (out_mats, exec_mats, scals) = scan_ticks(
            ncfg, view, inbox, step_impl, k)
        state = merge_view(state, view, off, fields)
        # the view's shifted window_base is an artifact (slide is off
        # in the view); report the real one
        scals = scals.at[:, SCAL_WINDOW_BASE].set(state.window_base)
        return state, out_mats, exec_mats, scals
    state, (out_mats, exec_mats, scals) = scan_ticks(
        cfg, state, inbox, step_impl, k)
    return state, out_mats, exec_mats, scals


@functools.partial(jax.jit, donate_argnums=0)
def _kv_install(kv, k_hi, k_lo, v, valid):
    """Batch-insert snapshot pairs into the KV table (snapshot keys
    are distinct by construction — the stable store sorts and the
    sender's table held them uniquely). Module-level jit like
    _packed_step: every replica in the process shares one compiled
    variant per (chunk, capacity) shape, and donation updates the
    table in place across the chunk loop."""
    return kv_insert_unique(kv, k_hi, k_lo, v,
                            delete=jnp.zeros_like(valid), valid=valid)


@dataclass
class _InflightTick:
    """One dispatched tick's host-phase inputs, already read back from
    the device. The pipeline completes these either immediately
    (serial order, -nopipeline or an empty queue) or one call later —
    between the NEXT tick's enqueue and readback, so persist/dispatch/
    reply run while the device computes (the hidden wall is recorded
    as the row's ``overlap_us``)."""

    cols: dict            # this tick's drained inbox columns
    n_rows: int
    out_mats: np.ndarray  # [k, 14, M] stacked outbox matrices
    exec_mats: np.ndarray  # [k, 6, E] stacked exec matrices
    scals: np.ndarray     # [k, N_SCAL] per-substep scalar vectors
    k: int
    kind: int             # recorder regime (KIND_FULL/FUSED/NARROW)
    persist: bool
    dispatch: bool
    frontier: int         # final (substep k-1) committed frontier
    backlog: int          # frontier - executed after the tick
    rows_out: int
    peer_commits: np.ndarray | None  # state's [R] vector (non-mencius)
    snap: dict            # the snapshot published at this readback
    wait_us: int
    drain_us: int
    enqueue_us: int
    readback_us: int
    wait_cpu_us: int      # the protocol thread's CPU time in each,
    drain_cpu_us: int     # measured if the row is ``sampled``
    sampled: bool
    t_rb_ns: int          # monotonic_ns at readback (trace anchoring)
    coal_occ: int = 0     # rows the ingress coalescer batched for this tick
    coal_wake: int = 0    # cumulative coalescer wakeup kicks at this tick
    # enqueue's two halves, and the CPU time of the dispatch phases:
    # the caller's, once the readback has ended
    assemble_us: int = 0
    call_us: int = 0
    enqueue_cpu_us: int = 0
    readback_cpu_us: int = 0
    assemble_cpu_us: int = 0
    call_cpu_us: int = 0


class FatalReplicaError(RuntimeError):
    """The replica can no longer execute correctly and must fail-stop
    (consensus tolerates a crashed replica; serving wrong data is the
    one thing it cannot tolerate)."""


@dataclass
class RuntimeFlags:
    """Server knobs — the reference's flag set (server.go:19-34).

    The reference's ``-exec`` (run executeCommands at all) has no
    counterpart here and is deliberately absent: execution is fused
    into the device step and drives sliding-window reclamation
    (models/minpaxos.py step 8 feeds step 9), so a non-executing
    replica would wedge its own log window. The CLI still accepts
    ``-exec`` for command-line compatibility; it is always on.
    """

    dreply: bool = True    # -dreply: reply after execution (with value)
    durable: bool = False  # -durable: fsync accepted slots per tick
    thrifty: bool = False  # -thrifty: send accepts to a quorum only
    beacon: bool = False   # -beacon: RTT beacons -> preferred quorum
    tick_s: float = 0.002  # protocol tick (reference clock: 5ms)
    # idle poll interval: a quiet replica wakes this often to drive
    # retries/stall detection. Message arrival always wakes it
    # immediately (queue.get), so this only prices background wakeups
    # — on a single-core host every idle tick preempts whoever is
    # doing real work, which directly inflates serial commit latency
    # (round-5 measurement: ~2x per-tick wall vs isolated).
    idle_s: float = 0.05
    # fused burst ticks: when the snapshot shows the batch will need
    # follow-up ticks (exec backlog beyond one exec_batch, lagging
    # catch-up/broadcast cursors), run this many protocol substeps in
    # ONE device dispatch (lax.scan, ops/substeps.py) instead of one
    # per host tick. 1 disables fusion.
    fuse_ticks: int = 3
    # idle fast path: when the inbox is empty and the published
    # snapshot's work_pending scalar says an empty step would be a
    # no-op, skip the device dispatch entirely — the idle-poll wakeups
    # then cost microseconds of host time instead of a full dispatch
    # (PERF.md: idle ticks stole ~2x per-tick wall on the 1-core
    # host). idle_skip_max_s bounds the skip streak: one real tick at
    # least this often, a belt-and-braces timer for anything the
    # work_pending derivation misses.
    idle_fastpath: bool = True
    idle_skip_max_s: float = 0.25
    # small-window specialized step: execute low-occupancy ticks
    # through a compiled-once narrow resident view of this many slots
    # (0 = off). Lets a server sized -window 16384 tick at the ~4x
    # cheaper W=512 cost the dedicated serial cluster measured,
    # falling back to the full-width step whenever the live span or
    # the inbox's addressed slots don't fit the view.
    narrow_window: int = 0
    # precompile the (k, narrow) step variants on the protocol thread
    # before serving (see _warm_step_variants). Default OFF: the
    # in-process test harnesses boot dozens of short-lived clusters
    # whose tests are calibrated to one lazy compile, and eager
    # warming blew their first-workload timeouts. The server CLI turns
    # it on — long-lived deployments must not pay a variant's first
    # compile mid-traffic.
    warm_variants: bool = False
    # operator's estimate of the workload's distinct-key count (0 =
    # unknown): start() logs projected KV load against the table
    # capacity, loudly, because saturation fail-stops the replica
    # (-kvpow2 footgun, VERDICT round-5 weak #5)
    key_hint: int = 0
    # depth-2 pipelined tick loop ("Paxos in the Cloud": pipelining is
    # the throughput lever next to batching): enqueue tick k's jitted
    # step WITHOUT blocking (JAX async dispatch), run tick k-1's
    # deferred host phases (persist -> dispatch -> reply, the -durable
    # fsync-before-reply ordering preserved per tick) while the device
    # computes, then read tick k back. Host phases are deferred ONLY
    # when follow-up traffic is already queued — a closed-loop serial
    # op (empty queue after its tick) completes immediately, so its
    # reply never waits for the next wakeup. -nopipeline restores the
    # strictly serial enqueue->readback->host order for A/Bs.
    pipeline: bool = True
    # event-driven ingress coalescer (batches.IngressCoalescer): the
    # inbox queue the transport's reader threads feed becomes a
    # condition-variable front that kicks the tick loop the moment
    # rows arrive and lingers up to coalesce_wait_us for more client
    # rows (stopping early at coalesce_rows) so concurrent sessions
    # share one dispatch. Admission control rides it: under exec-
    # backlog, window-full, or burn-rate overload (_ingress_overloaded)
    # client
    # PROPOSE frames beyond the pending bound are dropped at ingress
    # (clients retry) — bounded queueing instead of tail blowup. The
    # work_pending idle fast path is untouched (an idle replica still
    # parks on idle_s). -nocoalesce restores the plain queue.Queue;
    # coalesce_wait_us=0 keeps the kick but never lingers.
    coalesce: bool = True
    coalesce_wait_us: int = 200
    coalesce_rows: int = 0  # 0 = half the device inbox (sized at boot)
    # overlapped commit->exec->reply: when a dispatch's readback still
    # shows committed-but-unexecuted slots and no follow-up traffic is
    # queued, immediately run the follow-up dispatch in the SAME
    # wakeup instead of letting execution wait out the next poll
    # interval (the entire <exec_wait> paxtrace stage). The chased
    # dispatch is the identical deterministic step the next wakeup
    # would have run — byte-exact vs the strict-order path (pinned by
    # tests/test_coalescer.py) and no new compiled variant.
    # -nooverlapexec restores the one-dispatch-per-wakeup cadence.
    overlap_exec: bool = True
    # paxmon flight recorder (obs/recorder.py): per-tick ring logging
    # dispatch regime + per-phase wall, served over the control
    # socket's TRACE verb. Default ON — the recorder's hot-path cost
    # is one ring write per tick (the CI overhead guard in
    # tools/obs_smoke.py pins it); -norecorder disables for A/Bs.
    recorder: bool = True
    recorder_ring: int = 4096
    # paxtrace (obs/trace.py): sampled per-command stage spans served
    # over the control socket's TRACESPANS verb. Default ON at the
    # 1-in-2^trace_pow2 sample rate — unsampled commands pay one
    # vectorized hash per batch, sampled ones a handful of ring writes
    # (the obs_smoke per-command overhead guard pins the budget);
    # -notrace disables for A/Bs, trace_pow2=0 traces every command
    # (the serial-latency bench leg).
    trace: bool = True
    trace_pow2: int = 4
    trace_ring: int = 4096
    # paxwatch event journal (obs/watch.py): structured cluster events
    # (elections, leader changes, chaos installs, narrow fallbacks,
    # store-corruption recoveries, fail-stops, peer link up/down)
    # served over the control socket's EVENTS verb and rendered as
    # instant events in merged traces (schema v6). Default ON — a
    # journal write is one ring slice-assign plus two clock reads
    # (the obs_smoke <=5 us/event guard pins it); -nowatch disables.
    watch: bool = True
    watch_ring: int = 1024
    # paxdur snapshot + truncation policy (PR 20): checkpoint the
    # applied KV state into the stable store (stable.py REC_SNAPSHOT)
    # and truncate redo records below the PREVIOUS snapshot's frontier
    # — two snapshots are retained so a corrupt newest one falls back
    # to the older + a longer replay. The size trigger fires when the
    # on-disk log grows snap_every_bytes past the last snapshot
    # (-snap-every; 0 disables it); snap_interval_s adds a wall-clock
    # trigger (0 = off). -nosnap turns the whole policy off: the log
    # then grows unboundedly, exactly the pre-PR-20 behavior.
    snapshots: bool = True
    snap_every_bytes: int = 8 << 20
    snap_interval_s: float = 0.0
    store_dir: str = "."
    # -cpuprofile: a cProfile.Profile the PROTOCOL THREAD enables on
    # start (cProfile is per-thread; enabling it on the main thread —
    # the obvious wiring — would profile an idle sleep loop and dump
    # nothing, while all the work happens here)
    profile: object | None = None


class ReplicaServer:
    def __init__(self, me: int, addrs: list[tuple[str, int]],
                 cfg: MinPaxosConfig | None = None,
                 flags: RuntimeFlags | None = None,
                 protocol: str = "minpaxos"):
        self.me = me
        self.addrs = addrs
        self.cfg = cfg or MinPaxosConfig(
            n_replicas=len(addrs), window=1 << 14, inbox=4096,
            exec_batch=4096, kv_pow2=16, catchup_rows=256,
            recovery_rows=256)
        assert self.cfg.n_replicas == len(addrs)
        self.flags = flags or RuntimeFlags()
        # protocol selection (reference server.go:58-79 — where every
        # protocol but -min is commented out, mencius here actually
        # runs): "minpaxos" / "classic" share replica_step (classic via
        # cfg.explicit_commit); "mencius" swaps in the rotating-
        # ownership kernel. Leaderless paths (elections, leader-serving
        # catch-up, ballot-promise restore) are gated on self.protocol.
        self.protocol = protocol
        if protocol == "mencius":
            from minpaxos_tpu.models.mencius import (
                init_mencius,
                mencius_served_step_impl,
            )

            step_impl, init_fn = mencius_served_step_impl, init_mencius
        else:
            step_impl, init_fn = replica_step_impl, init_replica
        # paxmon registry (obs/metrics.py) — replaces the old bare
        # `stats` dict. Counter handles are bound once here so the hot
        # path pays one attribute add per advance; `self.stats` is now
        # a snapshot property (see below)
        self.metrics = MetricsRegistry(namespace=f"replica{me}")
        m = self.metrics
        self._c_ticks = m.counter(
            "ticks", "protocol-thread wakeups (WALL ticks — advances "
            "by tick_inc, never by fused substeps)")
        self._c_dispatches = m.counter("dispatches", "device round-trips")
        self._c_fused_substeps = m.counter(
            "fused_substeps", "protocol substeps those dispatches ran "
            "(>= dispatches under fusion)")
        self._c_full_steps = m.counter(
            "full_steps", "dispatches through the full-width k=1 step")
        self._c_fused_dispatches = m.counter(
            "fused_dispatches", "dispatches that fused k>1 substeps")
        self._c_narrow_steps = m.counter(
            "narrow_steps", "dispatches through the small-window view")
        self._c_idle_skips = m.counter(
            "idle_skips", "timer wakeups the idle fast path answered "
            "without touching the device")
        self._c_narrow_fallbacks = m.counter(
            "narrow_fallbacks", "narrow dispatches whose post-readback "
            "anchor validation failed; the next dispatch recounts "
            "through the full-width step")
        self._c_proposals = m.counter("proposals", "client command rows "
                                      "admitted to the inbox")
        self._c_rejected = m.counter(
            "proposals_rejected", "admitted command rows the kernel "
            "bounced back to the client (not leader / unprepared) — "
            "no log slot was assigned, so paxwatch's in-flight "
            "estimate (proposals - rejected - committed) subtracts "
            "them; without this a boot-window rejection burst biases "
            "the estimate high forever and an IDLE cluster looks "
            "permanently loaded to the stall detector")
        self._c_executed = m.counter("executed", "commands executed")
        # what the log is made of, per replica (the served twins of the
        # pod's command_commits / noop_slots): client rows this replica
        # gave a slot of its OWN (every replica is a proposer under
        # mencius, the leader alone otherwise), and the slots it
        # executed by kind. Every slot executes once, so at quiesce
        # noop_slots + command_slots is what the (merged) frontier
        # passed, and the cluster's client_proposals sum to
        # command_slots while no takeover re-drives a slot.
        self._c_client_proposals = m.counter(
            "client_proposals", "client command rows this replica "
            "assigned a log slot and broadcast as its own ACCEPTs")
        self._c_noop_slots = m.counter(
            "noop_slots", "executed slots that held no command: ceded "
            "(SKIP), takeover- or recovery-filled no-ops")
        self._c_command_slots = m.counter(
            "command_slots", "executed slots that held a client "
            "command, whichever replica proposed it")
        self._g_committed = m.gauge("committed",
                                    "committed prefix length (frontier+1)")
        self._h_tick = m.histogram(
            "tick_wall_ms", "whole-dispatch host wall (drain work + "
            "enqueue + readback + persist + dispatch + reply, wherever "
            "the host phases ran)", TICK_MS_BUCKETS)
        # who ran instead: the protocol thread's own CPU time, row by
        # row, beside its reader threads' (transport.py ingress_cpu_us)
        # and how many threads share the one GIL
        self._c_proto_cpu = m.counter(
            "proto_cpu_us", "the protocol thread's CPU time "
            "(thread_time_ns), the sum of every recorder row's cpu_us")
        self._g_threads = m.gauge(
            "threads_alive", "threading.active_count() at the newest "
            "recorder row: the threads that share this process's GIL")
        self.recorder = (FlightRecorder(self.flags.recorder_ring)
                         if self.flags.recorder else None)
        # the tick loop's phase clock (obs/recorder.py phase): every
        # interval the loop measures goes through it, into the row and,
        # under a profile, into the xplane as a paxos.tick.* span
        self._clock = PhaseClock(me)
        # the per-phase CPU times are measured for one row in
        # CPU_SAMPLE_EVERY: the clock's ``sample`` is set for the phases
        # of that row, dispatch side and host side (_sample_next_row)
        self._clock.sample = False
        self._rows_cut = 0
        # paxtrace sink: one per replica, shared with the transport's
        # reader threads (each thread gets its own ring inside). The
        # sink exists even when disabled so every touch point stays
        # one `.enabled` test.
        self.trace_sink = TraceSink(enabled=self.flags.trace,
                                    sample_pow2=self.flags.trace_pow2,
                                    ring_capacity=self.flags.trace_ring)
        m.fn_gauge("trace_spans", self.trace_sink.spans_total)
        m.fn_gauge("trace_dropped", self.trace_sink.spans_dropped)
        # readable by a harness in this process, also after stop()
        register_replica(me, m, self.recorder, self.trace_sink)
        # paxwatch journal: one per replica, shared with the
        # transport's reader threads (each writer thread gets its own
        # ring inside) — the journal exists even when disabled so
        # every touch point stays one `.enabled` test
        self.journal = EventJournal(enabled=self.flags.watch,
                                    capacity=self.flags.watch_ring)
        m.fn_gauge("events", self.journal.events_total)
        m.fn_gauge("events_dropped", self.journal.events_dropped)
        self._c_elections = m.counter(
            "elections", "become_leader rounds this replica ran "
            "(paxwatch churn detection reads the cluster-wide delta)")
        # sampled in-flight bookkeeping (protocol thread only): a
        # min-heap of (log slot, cmd_id) awaiting commit stamps
        # (bounded by the sampled in-flight count, 1-in-2^k of the
        # window; heap so the per-dispatch pop is O(covered), never a
        # scan of everything still above the frontier)
        self._trace_slots: list[tuple[int, int]] = []
        # mencius only: sampled own slots whose COMMIT row has not left
        # the device yet, slot -> cmd_id (the own_commit stamp; an
        # entry goes when its row leaves or the frontier passes it)
        self._trace_own: dict[int, int] = {}
        self._last_scals = None  # newest published scalar vector
        # ingress admission state — written by the protocol thread
        # (_update_burn), read lock-free by the coalescer's gate on
        # the transport reader threads (a plain bool + the published
        # snapshot; never self.state). Backlog bound: a few exec
        # batches of committed-but-unexecuted slots is normal pipeline
        # depth; an order of magnitude past it means execution lost
        # the race and new load must queue at the clients.
        self._admit_backlog_limit = max(8 * self.cfg.exec_batch, 256)
        # commit-bound overload (paxdur follow-up): when the device
        # window is within one exec batch of full, the kernel will
        # window-reject any admitted PROPOSE anyway — each reject
        # costs a device round trip plus a client retransmit, and on
        # a commit-bound cluster (durable appends, snapshot pauses)
        # that reject/retransmit loop is what melts the tail. Shed at
        # the door instead: same counted drop, none of the wasted work.
        self._admit_window_limit = self.cfg.window - self.cfg.exec_batch
        self._burn_hot = False
        self._burn_samples: deque[dict] = deque(maxlen=32)
        self._burn_last_s = 0.0
        # event-driven ingress front (tentpole of the p99-tail PR):
        # injected as the transport's inbox queue, so reader threads,
        # control verbs and beacons all feed the same cv-kicked,
        # batch-forming, admission-gated path. -nocoalesce falls back
        # to the transport's own queue.Queue.
        self.coalescer = (batches.IngressCoalescer(
            max_wait_us=self.flags.coalesce_wait_us,
            max_rows=self.flags.coalesce_rows or max(self.cfg.inbox // 2, 1),
            admit_gate=self._ingress_overloaded,
            metrics=self.metrics) if self.flags.coalesce else None)
        self.transport = Transport(me, addrs, inbox_queue=self.coalescer,
                                   metrics=self.metrics)
        self.transport.trace = self.trace_sink
        self.transport.journal = self.journal
        self.queue = self.transport.queue
        # the MODULE-level jitted packed step (static cfg + impl):
        # every replica in the process shares ONE compile cache — N
        # private jax.jit wrappers would compile the same kernel N
        # times concurrently, which starves small hosts (in-process
        # test clusters)
        cfg_ = self.cfg
        self.step = lambda state, inbox, k=1, narrow=0, off=0: _packed_step(
            cfg_, state, inbox, step_impl, k, narrow, off)
        # copy every leaf: jax caches/aliases equal small constants, and
        # donation rejects the same buffer appearing twice
        self.state = jax.tree_util.tree_map(
            lambda x: x.copy(), init_fn(self.cfg, me))
        self.store = StableStore(
            f"{self.flags.store_dir}/stable-store-replica{me}",
            sync=self.flags.durable, metrics=m, clock=self._clock)
        self._flushed_seen = 0  # store_flushed_bytes at the last row
        # CRC-rejected log records (stable.py replay): nonzero after a
        # recovery that skipped flipped-byte records — the holes self-
        # heal via peers, but the operator must see the disk went bad
        m.fn_gauge("store_corrupt_records",
                   lambda: self.store.corrupt_records)
        # paxdur durability gauges: the on-disk bound truncation
        # maintains, snapshot churn, and how stale the newest snapshot
        # is (paxtop's SNAP column reads these; -1 = never snapshotted)
        m.fn_gauge("store_log_bytes", self.store.log_bytes)
        m.fn_gauge("snap_count", lambda: self.store.snapshots_taken)
        m.fn_gauge("snap_age_s", self._snap_age_s)
        # snapshot policy state (protocol thread only): next log size
        # that triggers the size policy, last snapshot wall time, and
        # the policy-check rate limiter (log_bytes is a stat() call —
        # not per-tick material)
        self._snap_goal_bytes = max(self.flags.snap_every_bytes, 1)
        self._snap_last_s = time.monotonic()
        self._snap_check_s = 0.0
        self._snap_disabled = False
        # snapshot catch-up: per-peer pacing of pushes (a transfer in
        # flight must not be re-sent every tick) and the receive-side
        # assembly buffers keyed by the announced snapshot frontier
        self._snap_sent_s: dict[int, float] = {}
        self._snap_seq = 0
        self._snap_rx: dict[int, dict] = {}
        # crash-restart fault injection: crash() emulates a process
        # kill — no flush, no clean close, buffered store bytes lost
        self._crashed = False
        self.inbox = batches.ColumnBuffer(self.cfg.inbox)
        # reply bookkeeping: (conn_id, cmd_id) -> reply kind to send
        self._pending: dict[tuple[int, int], MsgKind] = {}
        self.rtt_ewma = np.full(len(addrs), np.inf)
        self._stop = threading.Event()
        self._recovered = self.store.recovered
        # fail-stop reason: set when the replica can no longer execute
        # correctly (e.g. KV table saturation — see _device_tick); the
        # control plane reports it so operators/tests see the cause
        self.fatal: str | None = None
        self._ctl_sock: socket.socket | None = None
        self._proto_thread: threading.Thread | None = None
        self._idle = False  # last step produced no work (throttle ticks)
        self._last_step = 0.0
        self._seen_leader = False  # any PREPARE/ACCEPT/COMMIT from a peer
        self._boot_pending: float | None = None  # deferred boot election
        # control-plane snapshot: the protocol thread swaps in a fresh
        # plain-Python dict each tick; other threads only ever read it.
        # They must NOT touch self.state — its arrays are donated into
        # the jitted step and die mid-tick. Keys here must match what
        # _device_tick publishes: readers (_mencius_store_answer, the
        # control plane) can run off a frame drained BEFORE the first
        # tick ever replaces this dict.
        # work_pending defaults True (no "low"/"high" keys yet): until
        # the first device tick publishes real scalars, the idle fast
        # path and the narrow view stay off
        self.snapshot = {"frontier": -1, "leader": -1, "prepared": False,
                         "window_base": 0, "work_pending": True}
        self._last_dispatch = 0.0  # wall time of the last device tick
        self._kv_warned = False  # one-shot near-saturation warning
        # pipeline state (protocol thread only): the one tick whose
        # host phases are deferred, and the narrow-view doubt flag the
        # post-readback anchor validation sets (next dispatch recounts
        # anchors through the full-width step)
        self._inflight: _InflightTick | None = None
        self._narrow_doubt = False
        # mencius only (_skip_rows_that_fit): per ceding owner, the
        # span of own slots its SKIP rows of the batch being gathered
        # cover, and the frame (or rest of one) held over for the next
        self._skip_span: dict[int, tuple[int, int]] = {}
        self._held = None

    @property
    def stats(self) -> dict:
        """Flat counter/gauge snapshot — a FRESH dict per read, taken
        under the registry lock. The old attribute handed out the live
        dict the tick thread was mutating, so a control-thread
        ``json.dumps`` (or a test comparing before/after) raced the
        protocol loop; a snapshot cannot."""
        return self.metrics.counters()

    # ---------------- lifecycle ----------------

    def start(self) -> None:
        self._log_kv_sizing()
        self.transport.listen()
        self._start_control()
        if self._recovered:
            self._recover_from_store()
        self.transport.connect_peers()
        self._proto_thread = threading.Thread(target=self._run, daemon=True)
        self._proto_thread.start()
        if self.flags.beacon:
            threading.Thread(target=self._beacon_loop, daemon=True).start()

    def _log_kv_sizing(self) -> None:
        """Loud, unconditional startup line: KV capacity vs the
        operator's workload hint. The table fail-stops on saturation
        (a dropped insert means silent state divergence), so -kvpow2
        vs distinct-key-count is an operational contract — state it
        where it cannot be missed instead of only in a flag help
        string (VERDICT round-5 weak #5)."""
        cap = 1 << self.cfg.kv_pow2
        hint = self.flags.key_hint
        msg = (f"replica {self.me}: KV table capacity {cap} "
               f"(-kvpow2 {self.cfg.kv_pow2}); fail-stops if the live "
               f"key space saturates it")
        if hint > 0:
            load = hint / cap
            msg += (f"; workload hint {hint} distinct keys -> "
                    f"projected load {load:.2f}")
            if load > 0.7:
                msg += (" — OVER the 0.7 comfort bound for two-choice "
                        "placement; raise -kvpow2 or expect fail-stop")
        else:
            msg += ("; no -keyhint given — size -kvpow2 so distinct "
                    "keys stay under ~0.7 of capacity")
        print(msg, file=sys.stderr, flush=True)

    def _check_kv_load(self) -> None:
        """Periodic near-saturation warning (one shot): counts live
        table slots off the hot path (every 1024 dispatches) so the
        operator hears about load > 0.7 BEFORE the kv.dropped
        fail-stop triggers."""
        if self._kv_warned or self._c_dispatches.value % 1024:
            return
        cap = 1 << self.cfg.kv_pow2
        live = int(np.asarray((self.state.kv.slot == LIVE).sum()))
        if live > 0.7 * cap:
            self._kv_warned = True
            print(f"replica {self.me}: KV table NEAR SATURATION — "
                  f"{live}/{cap} slots live (load {live / cap:.2f} > "
                  f"0.7); the replica fail-stops when an insert "
                  f"cannot place. Raise -kvpow2.",
                  file=sys.stderr, flush=True)

    def stop(self) -> bool:
        """Returns True when the protocol thread joined cleanly; False
        if it was still running at the join timeout (callers that dump
        its profiler state must not trust the data then)."""
        # order matters: signal, JOIN the protocol thread (it may be
        # mid-_persist), and only then close the store — the reference's
        # single event-loop goroutine gets this for free
        self._stop.set()
        joined = True
        if self._proto_thread is not None:
            self._proto_thread.join(timeout=10.0)
            joined = not self._proto_thread.is_alive()
        self.transport.stop()
        if self._ctl_sock is not None:
            try:
                self._ctl_sock.close()
            except OSError:
                pass
        self.store.close()
        return joined

    def crash(self) -> None:
        """paxchaos process-kill emulation: die like a SIGKILLed
        process, NOT like stop(). The store's buffered userspace bytes
        are lost (StableStore.crash — the on-disk file keeps only what
        already reached the kernel, possibly ending in a torn record),
        sockets close without flushing, no deferred host phase
        completes, and the control port goes dark so the master's
        observe fan-out sees a dead replica. In-process threads cannot
        be SIGKILLed, so this is the closest emulation the harness can
        run: every durable artifact matches a real kill."""
        self._crashed = True
        self.store.crash()
        self._stop.set()
        # wake the protocol thread immediately (it may be parked on an
        # idle-interval queue.get; the inbox queue is unbounded)
        self.queue.put((CONTROL, 0, "crashed", None))
        self.transport.stop()
        if self._ctl_sock is not None:
            try:
                self._ctl_sock.close()
            except OSError:
                pass
        if self._proto_thread is not None:
            self._proto_thread.join(timeout=10.0)

    def _snap_age_s(self) -> int:
        """Seconds since the newest retained snapshot (-1 = none) —
        wall-clock based so the age survives a restart."""
        w = self.store.snap_wall_ns
        if not w:
            return -1
        return max(0, int((time.time_ns() - w) // 1_000_000_000))

    # ---------------- recovery (stable-store replay) ----------------

    def _recover_from_store(self) -> None:
        """Rebuild device state by replaying the durable log through
        the SAME protocol kernel: committed prefix as COMMIT rows
        (commits + executes + rebuilds the KV + slides the window),
        accepted tail as ACCEPT rows. The reference's
        getDataFromStableStore (bareminpaxos.go:122-161) rebuilt Go
        structs; here recovery IS the protocol.

        Snapshot-first (PR 20): a truncated store replays as the
        newest CRC-valid snapshot's KV pairs installed directly into
        the table + the redo SUFFIX above its frontier — the records
        below it no longer exist on disk. A corrupt newest snapshot
        already fell back inside StableStore._replay (base = the
        previous snapshot, longer suffix), so this path never sees it."""
        t_rec0 = time.perf_counter()
        frontier = self.store.committed_prefix()
        max_ballot = self.store.max_ballot()
        chunk = self.cfg.exec_batch
        own_max = -1  # highest recorded slot owned by me (mencius)
        start = 0
        if self.store.base >= 0 and self.protocol != "mencius":
            self._install_snapshot_pairs(self.store.snapshot_pairs,
                                         self.store.base)
            start = self.store.base + 1

        def _own_slots_max(rec) -> int:
            mine = rec["inst"][rec["inst"] % self.cfg.n_replicas == self.me]
            return int(mine.max()) if len(mine) else -1

        for lo in range(start, frontier + 1, chunk):
            rec = self.store.read_range(lo, min(lo + chunk, frontier + 1) - 1)
            own_max = max(own_max, _own_slots_max(rec))
            self._feed_records(rec, MsgKind.COMMIT)
        tail = self.store.read_range(frontier + 1, self.store.max_inst())
        if len(tail):
            own_max = max(own_max, _own_slots_max(tail))
            self._feed_records(tail, MsgKind.ACCEPT)
        if self.protocol == "mencius":
            # no global ballot promise to restore. But crt_own MUST
            # move past every recorded own slot: the propose path
            # writes at crt_own unguarded (fresh slots by invariant),
            # so a stale cursor would overwrite recovered state. The
            # maximum is accumulated during the chunked replay above —
            # one whole-mirror read here would defeat that chunking.
            if own_max >= 0:
                self.state = self.state._replace(
                    crt_own=jnp.maximum(
                        self.state.crt_own,
                        jnp.int32(own_max + self.cfg.n_replicas)))
            # and the takeover ballots it drew before the crash are
            # never drawn again: one ballot, one value a slot
            self.state = self.state._replace(
                max_recv_ballot=jnp.maximum(self.state.max_recv_ballot,
                                            jnp.int32(max_ballot)))
        elif max_ballot > 0:
            # restore the ballot promise (ballot low 4 bits = proposer
            # id, bareminpaxos.go:383-385)
            buf = batches.ColumnBuffer(self.cfg.inbox)
            buf.append(1, kind=int(MsgKind.PREPARE), src=max_ballot % 16,
                       ballot=max_ballot,
                       last_committed=int(np.asarray(self.state.committed_upto)))
            self._device_tick(buf)
        if self.store.corrupt_records:
            # the stable store's replay already printed its (parser-
            # safe, byte-identical) warning lines; the journal makes
            # the recovery QUERYABLE — paxtop's HEALTH column and the
            # EVENTS fan-out see it without scraping stderr
            self.journal.record(EV_STORE_CORRUPT, subject=self.me,
                                value=self.store.corrupt_records)
        # EV_RECOVERY: the replica rebuilt serving state from durable
        # artifacts — value = the recovered frontier, aux = recovery
        # wall ms
        self.journal.record(
            EV_RECOVERY, subject=self.me, value=frontier,
            aux=int((time.perf_counter() - t_rec0) * 1e3))
        dlog(f"replica {self.me}: recovered frontier={frontier} "
             f"base={self.store.base} tail={len(tail)} "
             f"ballot={max_ballot}")

    def _feed_records(self, rec: np.ndarray, kind: MsgKind) -> None:
        if len(rec) == 0:
            return
        k_hi, k_lo = split_i64(rec["key"])
        v_hi, v_lo = split_i64(rec["val"])
        # row src: MinPaxos ballots encode the proposer in their low 4
        # bits; Mencius ownership is positional (owner = inst mod R,
        # mencius.go:431-432) and its accept guard checks exactly that
        src_all = (rec["inst"] % self.cfg.n_replicas
                   if self.protocol == "mencius" else rec["ballot"] % 16)
        for lo in range(0, len(rec), self.cfg.inbox):
            sl = slice(lo, lo + self.cfg.inbox)
            buf = batches.ColumnBuffer(self.cfg.inbox)
            buf.append(len(rec[sl]), kind=int(kind),
                       src=src_all[sl], ballot=rec["ballot"][sl],
                       inst=rec["inst"][sl],
                       last_committed=self.store.frontier,
                       op=rec["op"][sl].astype(np.int32),
                       key_hi=k_hi[sl], key_lo=k_lo[sl],
                       val_hi=v_hi[sl], val_lo=v_lo[sl],
                       cmd_id=rec["cmd_id"][sl],
                       client_id=rec["client_id"][sl])
            self._device_tick(buf, persist=False, dispatch=False)

    def _install_snapshot_pairs(self, pairs: np.ndarray,
                                frontier: int) -> None:
        """Fast-forward device state to a snapshot: install its live
        KV pairs (chunked through the module-jitted insert, fixed
        exec_batch shapes so no new compile per transfer size) and
        move every protocol cursor to frontier+1. The log-window
        arrays are re-zeroed — whatever they described is at/below the
        snapshot's frontier, which the installed table already covers
        — leaving exactly the state a replica that executed slots
        0..frontier and slid its window would hold. Scalars are fresh
        buffers (.copy()/computed) because the jitted step's donation
        rejects one buffer appearing twice."""
        chunk = max(self.cfg.exec_batch, 1)
        k_hi, k_lo = split_i64(np.ascontiguousarray(pairs["key"]))
        v_hi, v_lo = split_i64(np.ascontiguousarray(pairs["val"]))
        kv = self.state.kv
        for lo in range(0, len(pairs), chunk):
            n = min(chunk, len(pairs) - lo)
            ck_hi = np.zeros(chunk, np.int32)
            ck_lo = np.zeros(chunk, np.int32)
            cv = np.zeros((chunk, 2), np.int32)
            valid = np.zeros(chunk, bool)
            ck_hi[:n], ck_lo[:n] = k_hi[lo:lo + n], k_lo[lo:lo + n]
            cv[:n, 0], cv[:n, 1] = v_hi[lo:lo + n], v_lo[lo:lo + n]
            valid[:n] = True
            kv = _kv_install(kv, ck_hi, ck_lo, cv, valid)
        s = self.cfg.window
        fj = jnp.int32(frontier)
        self.state = self.state._replace(
            ballot=jnp.full(s, NO_BALLOT, jnp.int32),
            status=jnp.zeros(s, jnp.uint8),
            op=jnp.zeros(s, jnp.uint8),
            key_hi=jnp.zeros(s, jnp.int32),
            key_lo=jnp.zeros(s, jnp.int32),
            val_hi=jnp.zeros(s, jnp.int32),
            val_lo=jnp.zeros(s, jnp.int32),
            cmd_id=jnp.zeros(s, jnp.int32),
            client_id=jnp.zeros(s, jnp.int32),
            votes=jnp.zeros(s, jnp.uint16),
            pvotes=jnp.zeros(s, jnp.uint16),
            kv=kv,
            window_base=fj + 1,
            crt_inst=jnp.maximum(self.state.crt_inst, fj + 1),
            committed_upto=fj.copy(),
            executed_upto=fj.copy(),
            rec_cursor=jnp.maximum(self.state.rec_cursor, fj + 1),
            tenure_start=jnp.maximum(self.state.tenure_start, fj + 1),
            gossip_upto=fj.copy())

    # ---------------- control plane (port + 1000) ----------------

    def _start_control(self) -> None:
        host, port = self.addrs[self.me]
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        # retry: the control port (data port + 1000, the reference's
        # scheme) can transiently collide with an ephemeral outbound
        # port (e.g. a master ping's source port); those clear quickly
        deadline = time.monotonic() + 10.0
        while True:
            try:
                s.bind((host, port + CONTROL_OFFSET))
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.25)
        s.listen(16)
        self._ctl_sock = s
        threading.Thread(target=self._control_loop, daemon=True).start()

    def _control_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._ctl_sock.accept()
            except OSError:
                return
            threading.Thread(target=self._control_conn, args=(conn,),
                             daemon=True).start()

    def _control_conn(self, conn) -> None:
        f = conn.makefile("rw")
        try:
            for line in f:
                try:
                    req = json.loads(line)
                except json.JSONDecodeError:
                    break
                m = req.get("m")
                if m == "ping":
                    snap = self.snapshot  # one read: dict swap is atomic
                    resp = {"ok": self.fatal is None,
                            "frontier": snap["frontier"],
                            "leader": snap["leader"], "stats": self.stats,
                            "window_base": snap["window_base"],
                            "crt_inst": snap.get("crt_inst", -1),
                            "prepared": snap.get("prepared"),
                            "fatal": self.fatal}
                elif m == "stats":
                    # full typed snapshot (counters/gauges/histograms)
                    # plus the newest device-published scalar vector —
                    # everything here is a fresh copy; the tick thread
                    # is never exposed to the control connection
                    snap = self.snapshot
                    scals = self._last_scals
                    resp = {"ok": self.fatal is None, "id": self.me,
                            "protocol": self.protocol,
                            "leader": snap["leader"],
                            "frontier": snap["frontier"],
                            "window_base": snap["window_base"],
                            "executed": snap.get("executed", -1),
                            "work_pending": snap.get("work_pending", True),
                            "metrics": self.metrics.snapshot(),
                            "scalars": (None if scals is None else
                                        dict(zip(SCAL_NAMES,
                                                 scals.tolist()))),
                            "fatal": self.fatal}
                elif m == "trace":
                    # flight-recorder export as Chrome trace events
                    # (pid = replica id so merged cluster traces keep
                    # one track group per replica); "last" bounds the
                    # response size for pollers
                    last = req.get("last")
                    events = ([] if self.recorder is None else
                              self.recorder.to_events(
                                  pid=self.me,
                                  last=int(last) if last else 1024))
                    if self.recorder is not None and self.journal.enabled:
                        # paxwatch journal rides the merged timeline
                        # as instant events on the reserved WATCH_PID
                        # (schema v6), one tid per replica. Gated on
                        # the recorder too: -norecorder keeps TRACE
                        # answering empty-but-ok (pinned by test), and
                        # the journal stays queryable via EVENTS.
                        events += event_chrome_events(
                            self.journal.snapshot(), tid=self.me)
                    resp = {"ok": True, "id": self.me,
                            "recorder": self.recorder is not None,
                            "events": events}
                elif m == "events":
                    # paxwatch EVENTS verb: the journal's retained
                    # events (every writer thread's ring) plus the
                    # (mono, wall) clock anchor align_event_collections
                    # shifts processes into one domain by
                    resp = {"ok": True, "id": self.me,
                            "journal": self.journal.collect()}
                elif m == "tracespans":
                    # paxtrace collection: every span ring of this
                    # process (protocol thread, transport readers) plus
                    # the monotonic<->wall clock anchor tail.py aligns
                    # processes by. The copy is taken under the sink's
                    # tiny locks; the writers never block.
                    resp = {"ok": True, "id": self.me,
                            "trace": self.trace_sink.collect()}
                elif m == "chaos":
                    # paxchaos verb: install/clear/status a fault plan
                    # on the LIVE transport. Installing is an attribute
                    # swap the reader threads observe per frame, so a
                    # partition can be flipped mid-workload; status
                    # reports per-kind injected-fault tallies.
                    resp = self._chaos_verb(req)
                elif m == "phase":
                    # paxsoak verb: journal a scenario-phase boundary
                    # (EV_PHASE) on THIS replica's journal so phase
                    # edges share the detector/chaos monotonic domain.
                    # Journaled from this control thread's own ring,
                    # the established _chaos_verb pattern.
                    self.journal.record(
                        EV_PHASE, subject=int(req.get("ordinal", 0)),
                        value=int(req.get("duration_ms", 0)),
                        aux=int(req.get("kind_id", 0)))
                    resp = {"ok": True, "id": self.me}
                elif m == "be_the_leader":
                    self.queue.put((CONTROL, 0, "be_the_leader", None))
                    resp = {"ok": True}
                else:
                    resp = {"ok": False, "error": f"unknown method {m}"}
                f.write(json.dumps(resp) + "\n")
                f.flush()
        except (OSError, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _chaos_verb(self, req: dict) -> dict:
        op = req.get("op", "status")
        try:
            if op == "install":
                plan = FaultPlan.from_dict(req["plan"])
                if plan.n != self.cfg.n_replicas:
                    raise ValueError(
                        f"plan sized for {plan.n} replicas, cluster "
                        f"has {self.cfg.n_replicas}")
                self.transport.set_chaos(
                    ChaosShim(self.me, plan, self.queue))
                # journaled from this control thread's own ring: a
                # campaign's fault window is queryable next to the
                # alarms it provoked (value = the plan's seed)
                self.journal.record(EV_CHAOS_INSTALL, subject=self.me,
                                    value=int(plan.seed))
            elif op == "clear":
                self.transport.set_chaos(None)
                self.journal.record(EV_CHAOS_CLEAR, subject=self.me)
            elif op != "status":
                raise ValueError(f"unknown chaos op {op!r}")
        except (KeyError, TypeError, ValueError) as e:
            return {"ok": False, "id": self.me, "error": repr(e)[:200]}
        ch = self.transport.chaos
        return {"ok": True, "id": self.me, "installed": ch is not None,
                "faults": ch.counts() if ch is not None else {},
                "faults_total": self.transport.chaos_faults_total()}

    # ---------------- beacons ----------------

    def _beacon_loop(self) -> None:
        """Reference SendBeacon/ReplyBeacon + EWMA RTT
        (genericsmr.go:537-551, :429). This thread only ENQUEUES the
        beacon; the protocol thread writes it — peer writers are
        single-threaded by contract (transport.py), and a concurrent
        write racing the protocol thread's flush is silently dropped
        (append between flush's snapshot and clear)."""
        while not self._stop.is_set():
            self.queue.put((CONTROL, 0, "send_beacon", None))
            time.sleep(0.2)

    # ---------------- the protocol loop ----------------

    def _warm_step_variants(self) -> None:
        """Compile every (k, narrow) step variant the tick loop can
        select BEFORE serving traffic: a variant first compiled
        mid-trial stalls the protocol thread for seconds — long enough
        for client retry timeouts and duplicate replies (observed when
        the need-scaled k=2 variant first compiled inside a bench
        trial). With the persistent compile cache this is a cache load
        on every boot after the first. Runs on the protocol thread
        (same thread that ticks), on empty inboxes; the handful of
        consumed tick counters is boot noise."""
        empty = MsgBatch(
            **{c: np.zeros(self.cfg.inbox, np.int32) for c in batches.COLS})
        nw = self.flags.narrow_window
        narrows = [0] + ([nw] if nw and nw < self.cfg.window else [])
        ks = {1, max(1, self.flags.fuse_ticks)}  # k is quantized to these
        for k in sorted(ks):
            for narrow in narrows:
                self.state, *_ = self.step(self.state, empty, k, narrow, 0)

    def _run(self) -> None:
        prof = self.flags.profile
        if prof is not None:
            prof.enable()
        try:
            self._clock.adopt()  # CPU time is this thread's from here
            # this thread stamps four of a command's stages: its ring
            # is sized to hold a whole benchmark window of them
            self.trace_sink.ring(protocol_ring_capacity(
                self.cfg.window, self.flags.trace_pow2,
                self.flags.trace_ring))
            if self.flags.warm_variants:
                self._warm_step_variants()
            if (not self._recovered and self.me == 0
                    and self.protocol != "mencius"):
                # initial boot: replica 0 self-elects
                # (bareminpaxos.go:286-290); wait until the mesh is up
                # so the PREPARE reaches everyone. Mencius has no
                # leader — every replica proposes into its own slots.
                self._wait_for_peers()
                self.queue.put((CONTROL, 0, "be_the_leader", "boot"))
            while not self._stop.is_set():
                self._tick()
            # clean shutdown: complete any deferred host phases so the
            # last tick's replies/persistence aren't dropped with the
            # thread (a FATAL tick deliberately skips this — fail-stop
            # must not keep serving; a crash() drops them by design —
            # a killed process never got to flush either)
            if not self._crashed:
                self._flush_inflight()
        except FatalReplicaError as e:
            # fail-stop: stop serving; the control plane keeps
            # answering pings with ok=False + the fatal reason
            print(f"FATAL: {e}", file=sys.stderr, flush=True)
        except Exception:
            # a crash() races the protocol thread mid-tick (closed
            # sockets, swapped store fd): any exception it provokes is
            # the kill itself, not a bug — die quietly like the killed
            # process would. Everything else propagates.
            if not self._crashed:
                raise
        finally:
            if prof is not None:
                prof.disable()

    def _wait_for_peers(self, timeout_s: float = 15.0) -> None:
        deadline = time.monotonic() + timeout_s
        need = self.cfg.n_replicas - 1
        while time.monotonic() < deadline and not self._stop.is_set():
            n = sum(self.transport.peer_alive(q)
                    for q in range(self.cfg.n_replicas) if q != self.me)
            if n >= need:
                return
            for q in range(self.me):
                if not self.transport.peer_alive(q):
                    self.transport.dial_peer(q)
            time.sleep(0.05)

    # SLO the replica-local burn evaluation runs against (the paxwatch
    # SLO dataclass defaults, on a window short enough for admission
    # to react within a couple of seconds)
    _BURN_SLO_MS = 50.0
    _BURN_WINDOW_S = 2.0

    def _ingress_overloaded(self) -> bool:
        """Admission signal for the ingress coalescer — called by the
        transport's READER threads, so it reads only the published
        snapshot and a plain bool (never ``self.state``). Overload =
        the paxmon exec backlog (committed-but-unexecuted) beyond the
        boot-sized bound, the device window nearly full (commits are
        the bottleneck — a commit-bound cluster would window-reject
        the rows downstream at full device-round-trip cost, so the
        occupancy arm sheds them at the door before the reject/
        retransmit loop amplifies the load), or the replica-local
        paxwatch burn-rate alarm (_update_burn). The coalescer turns
        a True verdict into counted ingress drops once its own
        pending bound is exceeded — bounded queueing at the clients
        instead of tail blowup."""
        snap = self.snapshot
        fr = int(snap.get("frontier", -1))
        ex = int(snap.get("executed", fr))
        wb = int(snap.get("window_base", 0))
        return (fr - ex > self._admit_backlog_limit
                or fr - wb >= self._admit_window_limit
                or self._burn_hot)

    def _update_burn(self, now: float) -> None:
        """Feed the tick-wall histogram's cumulative bad/total pair
        through the SAME ``burn_alarm`` detector the cluster watcher
        runs (obs/watch.py), replica-locally at ~4 Hz, and edge-journal
        the verdict — the admission gate's second input. The bad-bucket
        derivation mirrors ``flatten_cluster_stats``: a bucket is bad
        when its LOWER edge clears the SLO; the overflow bin is always
        bad."""
        if now - self._burn_last_s < 0.25:
            return
        self._burn_last_s = now
        h = self._h_tick
        bad = sum(c for i, c in enumerate(h.counts)
                  if i == len(h.counts) - 1
                  or (0 < i <= len(h.bounds)
                      and h.bounds[i - 1] >= self._BURN_SLO_MS))
        self._burn_samples.append({"t": now, "hist_total": h.total,
                                   "hist_bad": bad, "replicas": {}})
        alarm = burn_alarm(list(self._burn_samples),
                           window_s=self._BURN_WINDOW_S,
                           slo_ms=self._BURN_SLO_MS)
        hot = alarm is not None
        if hot and not self._burn_hot:
            self.journal.record(
                EV_ALARM, subject=self.me,
                value=int(alarm["evidence"].get("window_s", 0) * 1e3),
                aux=DET_BURN)
        elif self._burn_hot and not hot:
            self.journal.record(EV_ALARM_CLEAR, subject=self.me,
                                aux=DET_BURN)
        self._burn_hot = hot

    def _tick(self) -> None:
        # idle throttle: a quiet replica (empty inbox, no output, no
        # pending execution last step) steps at ~20Hz instead of every
        # tick_s — incoming messages still trigger an immediate step
        # via the queue wakeup. Keeps an idle N-replica in-process
        # cluster from saturating small hosts with no-op device steps.
        timeout = self.flags.idle_s if self._idle else self.flags.tick_s
        # one wakeup = one WALL tick: fused device substeps (k > 1)
        # and skipped dispatches alike ride this single increment
        # (paxlint wall-honesty — a k-advance here would age the tick
        # counter k times faster than wall time)
        tick_inc = 1
        elect = self._drain(timeout)
        self._update_burn(time.monotonic())
        if (self._boot_pending is not None
                and time.monotonic() >= self._boot_pending):
            self._boot_pending = None
            stale = (self._seen_leader
                     or self.snapshot["frontier"] >= 0
                     or self.snapshot["leader"] not in (-1, self.me))
            if stale:
                dlog(f"replica {self.me}: skipping stale boot "
                     f"self-election (leader={self.snapshot['leader']},"
                     f" frontier={self.snapshot['frontier']})")
            else:
                elect = True
        if (self._idle and not elect and self.inbox.fill == 0
                and time.monotonic() - self._last_step < self.flags.idle_s):
            # going quiet: deferred host phases must not sit out the
            # idle window (their replies/broadcasts are already late
            # by one enqueue — never by a poll interval)
            self._flush_inflight()
            return
        # idle fast path: the device itself said (work_pending scalar,
        # published with the last snapshot) that an empty-inbox step
        # would be a no-op — skip the dispatch entirely instead of
        # burning a 0.3-0.9 ms device round trip per idle poll. A real
        # tick still runs at least every idle_skip_max_s as a safety
        # net, and any drained frame or election falls through.
        if (self.flags.idle_fastpath and not elect
                and self.inbox.fill == 0
                and not self.snapshot.get("work_pending", True)
                and time.monotonic() - self._last_dispatch
                < self.flags.idle_skip_max_s):
            self._flush_inflight()  # see the idle-throttle note above
            self._c_idle_skips.inc()
            self._c_ticks.inc(tick_inc)
            clock = self._clock
            wait_cpu_us = clock.take_cpu_us(PH_WAIT)
            drain_cpu_us = clock.take_cpu_us(PH_DRAIN)
            sampled = clock.sample
            cpu_us = clock.cpu_us(sampled)
            self._c_proto_cpu.inc(cpu_us)
            if self.recorder is not None:
                self.recorder.record(
                    monotonic_ns(), KIND_IDLE_SKIP, 0, 0, 0,
                    self.snapshot["frontier"], 0,
                    clock.take_us(PH_DRAIN), 0, 0, 0, 0, 0, 0,
                    chaos_faults=self.transport.chaos_faults_total(),
                    coal_wake=(self.coalescer._c_wakeups.value
                               if self.coalescer is not None else 0),
                    wait_us=clock.take_us(PH_WAIT),
                    cpu_us=cpu_us, wait_cpu_us=wait_cpu_us,
                    drain_cpu_us=drain_cpu_us, cpu_sampled=sampled)
            self._sample_next_row()
            # skipping IS being idle: without this the next poll waits
            # only tick_s (2 ms) and a quiet replica spins the skip
            # check at 500 Hz instead of idle_s pacing
            self._idle = True
            # _drain can have BUFFERED frames this iteration without
            # making the inbox non-empty (beacons, beacon replies) —
            # flush them now or they sit until the safety-net tick and
            # the RTT EWMA measures buffering delay instead of network
            # (flush_all on empty writers is a cheap no-op)
            self.transport.flush_all()
            return
        if elect:
            self._become_leader()
            self._last_elect = time.monotonic()
        elif (self.snapshot["leader"] == self.me
              and not self.snapshot["prepared"]
              and time.monotonic() - getattr(self, "_last_elect", 0.0) > 0.5):
            # the one-shot PREPARE broadcast can be lost (a peer mid
            # store-replay or reconnecting isn't reading yet), which
            # would wedge an elected leader unprepared forever — re-run
            # the prepare round at a fresh ballot until majority answers
            self._become_leader()
            self._last_elect = time.monotonic()
        self._device_tick(self.inbox)
        # overlapped commit->exec->reply (the exec chase): a slot this
        # dispatch committed executes in the NEXT dispatch — which,
        # with an empty queue, used to fire only after the poll
        # timeout: the entire <exec_wait> paxtrace stage. Run the
        # follow-up dispatch(es) in THIS wakeup while backlog remains
        # and no fresh traffic is queued. Each chased dispatch is the
        # identical deterministic step the next wakeup would have run
        # (same fuse/narrow decision inputs, no new compiled variant),
        # so replies and state are byte-exact vs the strict cadence —
        # merely earlier in wall time. Bounded, with a forward-
        # progress check: a wedged backlog (execution blocked on a
        # commit hole) must park on the poll loop, not spin here.
        if self.flags.overlap_exec:
            for _ in range(8):
                snap = self.snapshot
                prev_exec = int(snap.get("executed", -1))
                if (snap["frontier"] <= prev_exec or self.inbox.fill
                        or self._more_queued()):
                    break
                self._device_tick(self.inbox)
                if int(self.snapshot.get("executed", -1)) <= prev_exec:
                    break  # no forward progress: stop chasing
        self._maybe_snapshot()
        self._last_step = time.monotonic()
        self._c_ticks.inc(tick_inc)

    def _maybe_snapshot(self) -> None:
        """Snapshot + truncation policy (protocol thread, after the
        tick's dispatches): checkpoint once the on-disk log grew
        snap_every_bytes past the last snapshot, or snap_interval_s
        elapsed with new execution. Rate-limited to 4 Hz — the size
        probe is a stat() call, not per-tick material. Mencius is
        gated off: its recovery replays the full log (ownership
        cursors have no snapshot restore), so truncating under it
        would orphan its own restart."""
        fl = self.flags
        if (not fl.snapshots or self._snap_disabled or self._crashed
                or self.protocol == "mencius" or self.fatal is not None):
            return
        now = time.monotonic()
        if now < self._snap_check_s:
            return
        self._snap_check_s = now + 0.25
        exec_upto = int(self.snapshot.get("executed", -1))
        if exec_upto < 0 or exec_upto <= self.store.snap_frontier:
            return  # nothing newly applied to checkpoint
        size_due = (fl.snap_every_bytes > 0
                    and self.store.log_bytes() >= self._snap_goal_bytes)
        time_due = (fl.snap_interval_s > 0
                    and now - self._snap_last_s >= fl.snap_interval_s)
        if size_due or time_due:
            # checkpointing is persistence work: a snapshot's pause
            # reads as persist (and its fsyncs as fsync), not as wait
            with phase(PH_PERSIST, self._clock):
                self._take_snapshot(exec_upto)

    def _take_snapshot(self, exec_upto: int) -> None:
        """Checkpoint the applied KV state at ``exec_upto`` into the
        stable store and truncate the redo log (one atomic segment
        swap, stable.py take_snapshot — two snapshots retained for the
        corruption-fallback ladder). Runs between dispatches, so
        ``self.state``'s buffers are alive and the published snapshot
        corresponds exactly to them; deferred host phases complete
        first so every record at/below exec_upto is in the store
        before the rewrite."""
        self._flush_inflight()
        kv = self.state.kv
        live = np.asarray(kv.slot) == LIVE
        keys = join_i64(np.asarray(kv.key_hi)[live],
                        np.asarray(kv.key_lo)[live])
        v = np.asarray(kv.val)
        vals = join_i64(v[live, 0], v[live, 1])
        freed = self.store.take_snapshot(keys, vals, exec_upto,
                                         wall_ns=time.time_ns())
        if freed == -1:
            # v1 store file (no CRC framing to protect a snapshot):
            # the policy can never succeed on this file — stop probing
            self._snap_disabled = True
            return
        lb = self.store.log_bytes()
        # EV_SNAPSHOT: value = checkpointed frontier, aux = log bytes
        # after; EV_TRUNCATE only when disk actually shrank (the first
        # snapshot truncates nothing): value = bytes freed
        self.journal.record(EV_SNAPSHOT, subject=self.me,
                            value=exec_upto, aux=lb)
        if freed > 0:
            self.journal.record(EV_TRUNCATE, subject=self.me,
                                value=freed, aux=lb)
        self._snap_goal_bytes = lb + max(self.flags.snap_every_bytes, 1)
        self._snap_last_s = time.monotonic()
        dlog(f"replica {self.me}: snapshot@{exec_upto} "
             f"({len(keys)} pairs, freed {freed} B, log {lb} B)")

    def _sample_next_row(self) -> None:
        """A row's dispatch-side fields were just cut: whatever the
        loop measures from here on belongs to the next row, whose
        per-phase CPU times are read or not as a whole."""
        self._rows_cut += 1
        self._clock.sample = self._rows_cut % CPU_SAMPLE_EVERY == 0

    def _drain(self, timeout_s: float) -> bool:
        """Pull queued frames into the inbox buffer; returns whether a
        be_the_leader control event arrived."""
        item, self._held = self._held, None
        if item is None:
            try:
                # the blocking wait is its own phase: idle pacing is
                # not drain cost, but it is where a loaded tick's wall
                # can go
                with phase(PH_WAIT, self._clock):
                    item = self.queue.get(timeout=timeout_s)
            except queue.Empty:
                return False
        with phase(PH_DRAIN, self._clock):
            return self._drain_frames(item)

    def _more_queued(self) -> bool:
        """Whether the next dispatch already has a frame to carry."""
        return self._held is not None or not self.queue.empty()

    def _skip_rows_that_fit(self, rows) -> int:
        """How many leading rows of a peer's SKIP frame may join the
        batch being gathered (mencius). The kernel folds ALL of one
        owner's SKIP rows of a batch into ONE range, least start to
        greatest end (models/mencius.py section 4): exact while the
        rows' ranges touch, wrong when the owner proposed between two
        cedes — its value, lying between the ranges, would be
        committed here as a no-op (served Mencius on three loaded
        owners sends cede, ACCEPT, cede in one TCP read; the pod routes
        one SKIP row an owner a round and never does). So a row joins
        only if no own slot of its owner lies between its range and
        what the batch already holds of that owner; the rest of the
        frame waits for the next dispatch."""
        r = self.cfg.n_replicas
        for i, (owner, start, end) in enumerate(zip(
                rows["leader_id"].tolist(), rows["start_inst"].tolist(),
                rows["end_inst"].tolist())):
            lo, hi = self._skip_span.get(owner, (start, end))
            if start > hi + r or end < lo - r:
                return i
            self._skip_span[owner] = (min(lo, start), max(hi, end))
        return len(rows)

    def _drain_frames(self, item) -> bool:
        """The work of a drain — decode, dedup, registration — from the
        first dequeued item until the queue or the inbox's room runs
        out."""
        elect = False
        while True:
            src_kind, conn_id, kind, rows = item
            if src_kind == CONTROL:
                if kind == "be_the_leader":
                    # the BOOT self-election is a cold-start convenience
                    # (bareminpaxos.go:286-290), not an authority claim:
                    # if this replica's first tick was delayed (a long
                    # first jit compile on a loaded host) the cluster
                    # may already have an active leader + committed
                    # prefix — deposing it with an empty log wedged the
                    # cluster at the old leader's last catch-up chunk
                    # (round-5 wedge hunt). Defer the decision half a
                    # second of ticking (_tick settles it) so traffic
                    # racing the boot event can land first. Master
                    # promotions (rows is None) stay unconditional: the
                    # master knows more than we do.
                    if rows == "boot":
                        self._boot_pending = time.monotonic() + 0.5
                    else:
                        elect = True
                elif kind == "send_beacon":
                    rows = make_batch(MsgKind.BEACON, rid=self.me,
                                      timestamp=np.uint64(cputicks()))
                    for q in range(self.cfg.n_replicas):
                        if q != self.me:
                            self.transport.send_peer(q, MsgKind.BEACON,
                                                     rows)
            elif src_kind == CONN_LOST:
                pass  # peer redial is lazy (dispatch path)
            elif kind == MsgKind.BEACON:
                self.transport.send_peer(
                    int(rows["rid"][0]), MsgKind.BEACON_REPLY, rows)
            elif kind == MsgKind.BEACON_REPLY:
                rtt = cputicks() - int(rows["timestamp"][0])
                # the replier echoes the beacon unchanged, so rid is OUR
                # id; the peer is the connection the reply came in on
                q = conn_id if src_kind == FROM_PEER else int(rows["rid"][0])
                if q != self.me:
                    old = self.rtt_ewma[q]
                    self.rtt_ewma[q] = (rtt if np.isinf(old)
                                        else 0.99 * old + 0.01 * rtt)
            elif kind == MsgKind.READ:
                # linearizable read: goes through the log as a GET
                # (the reference parses-and-drops READ,
                # genericsmr.go:470-477; we serve it)
                n = len(rows)
                k_hi, k_lo = split_i64(rows["key"])
                self.inbox.append(
                    n, kind=int(MsgKind.PROPOSE), src=-1, op=int(Op.GET),
                    key_hi=k_hi, key_lo=k_lo, cmd_id=rows["cmd_id"],
                    client_id=conn_id)
                for c in rows["cmd_id"]:
                    self._pending[(conn_id, int(c))] = MsgKind.READ_REPLY
            elif kind == MsgKind.TRACE_CTX:
                # paxtrace context (host-path verb, never a device
                # row): echo the client's origin timestamp as the
                # chain's start span, RE-STAMPED into this replica's
                # monotonic domain (wall minus OUR wall-mono offset —
                # an exact identity when client and replica share a
                # host, the honest correction when they don't).
                # Filtered through OUR sampling exponent: a client
                # tracing more aggressively than the cluster must
                # degrade to the intersection, not flood the protocol
                # thread's ring with ORIGIN rows whose chains can
                # never complete.
                if self.trace_sink.enabled and len(rows):
                    m = self.trace_sink.sampled(rows["cmd_id"])
                    if m.any():
                        ring = self.trace_sink.ring()
                        my_off = time.time_ns() - monotonic_ns()
                        take = rows[m]
                        for cmd, tid, wall in zip(
                                take["cmd_id"].tolist(),
                                take["trace_id"].tolist(),
                                take["origin_wall_ns"].tolist()):
                            ring.record(tid, ST_ORIGIN, wall - my_off,
                                        wall - my_off, cmd)
            elif kind == MsgKind.SNAP_META:
                # snapshot catch-up announcement (host-path verb, like
                # TRACE_CTX — never a device row): open an assembly
                # buffer per announced frontier. Only transfers ahead
                # of our own executed frontier are worth assembling.
                for r in rows:
                    fr = int(r["frontier"])
                    if (fr > int(self.snapshot.get("executed", -1))
                            and fr not in self._snap_rx):
                        self._snap_rx[fr] = {"count": int(r["count"]),
                                             "src": int(r["leader_id"]),
                                             "rows": []}
                self._snap_rx_install()  # count=0 installs immediately
            elif kind == MsgKind.SNAP_ROWS:
                # pairs for an announced transfer; the per-row frontier
                # keys each row to ITS snapshot, so interleaved or
                # re-sent transfers can't splice
                for fr in np.unique(rows["frontier"]):
                    st = self._snap_rx.get(int(fr))
                    if st is not None:
                        st["rows"].append(rows[rows["frontier"] == fr])
                self._snap_rx_install()
            else:
                if src_kind == FROM_PEER and kind in (
                        MsgKind.PREPARE, MsgKind.ACCEPT, MsgKind.COMMIT,
                        MsgKind.COMMIT_SHORT):
                    # sticky: leader-originated traffic exists, so a
                    # still-queued boot self-election is stale even if
                    # the snapshot hasn't caught up yet (first drain
                    # runs before the first device tick)
                    self._seen_leader = True
                if src_kind == FROM_CLIENT and kind == MsgKind.PROPOSE:
                    # drop same-connection re-sends of still-pending
                    # commands: the client's retry driver re-proposes
                    # unacked ids after a timeout, and admitting the
                    # re-send would allocate a SECOND log slot (and a
                    # second reply) for a command that is merely slow —
                    # under load that amplifies into a retry storm
                    # (each re-proposal adds slots, slowing commits,
                    # causing more timeouts; Mencius's blocking
                    # frontier made this a death spiral, round 5). A
                    # failed-over client arrives on a NEW connection
                    # and is admitted as before.
                    fresh = np.fromiter(
                        ((conn_id, int(c)) not in self._pending
                         for c in rows["cmd_id"]), bool, len(rows))
                    if not fresh.all():
                        rows = rows[fresh]
                    # truncate to inbox room BEFORE registering: a row
                    # registered but dropped by ColumnBuffer overflow
                    # would make the dedup blackhole its retries (the
                    # reply that pops the pending entry never comes)
                    rows = rows[:max(self.inbox.room(), 0)]
                    for c in rows["cmd_id"]:
                        self._pending[(conn_id, int(c))] = MsgKind.PROPOSE_REPLY
                    self._c_proposals.inc(len(rows))
                    if self.trace_sink.enabled and len(rows):
                        # drain stamp for sampled commands; aux = the
                        # dispatch counter, so tail.py can say how many
                        # device rounds admission -> execution took
                        # (the flight-recorder row correlation)
                        t_dr = monotonic_ns()
                        self.trace_sink.stamp_batch(
                            ST_DRAIN, rows["cmd_id"], t_dr, t_dr,
                            aux=self._c_dispatches.value)
                    if DLOG:
                        dlog(f"replica {self.me}: drain PROPOSE "
                             f"n={len(rows)}")
                if kind == MsgKind.PREPARE_INST:
                    # beyond-retention heal, ALL protocols: a sweep
                    # (mencius takeover, or a re-elected laggard
                    # leader's phase-1 sweep) asks about slots we
                    # already slid out; the device can't answer (out of
                    # window) but the stable store's mirror can — serve
                    # the range as COMMIT rows. Without this, a leader
                    # elected with a stale log wedges forever once its
                    # sweep reaches slots beyond every follower's
                    # window (round-5 wedge hunt).
                    self._store_answer_sweep(rows)
                if kind == MsgKind.SKIP and self.protocol == "mencius":
                    n_fit = self._skip_rows_that_fit(rows)
                    if n_fit < len(rows):
                        self._held = (src_kind, conn_id, kind, rows[n_fit:])
                        batches.frame_to_rows(self.inbox, kind,
                                              rows[:n_fit], conn_id)
                        break
                batches.frame_to_rows(self.inbox, kind, rows, conn_id)
            if self.inbox.room() <= 0:
                break
            try:
                item = self.queue.get_nowait()
            except queue.Empty:
                break
        return elect

    def _store_commit_frame(self, lo: int, hi: int, frontier: int):
        """A COMMIT wire frame of store-mirror records for [lo, hi],
        or None if no records exist — the building block of both
        store-served heal paths (_host_catchup, _mencius_store_answer)."""
        rec = self.store.read_range(lo, hi)
        if len(rec) == 0:
            return None
        return make_batch(
            MsgKind.COMMIT, leader_id=self.me, inst=rec["inst"],
            ballot=rec["ballot"], op=rec["op"], key=rec["key"],
            val=rec["val"], cmd_id=rec["cmd_id"],
            client_id=rec["client_id"], last_committed=frontier)

    def _store_answer_sweep(self, rows) -> None:
        """Serve a PREPARE_INST sweep that reaches below our window
        from the durable mirror: COMMIT rows for [lowest asked slot,
        committed prefix], chunked by catchup_rows. Not capped at the
        asked range — the laggard's crt_inst advances from the commits
        it applies, which is what lets its next sweep reach further
        (its own view of the log tip is stale by exactly the gap).
        Serves mencius takeover sweeps and minpaxos/classic new-leader
        phase-1 sweeps alike."""
        base = self.snapshot["window_base"]
        lo = int(rows["inst"].min())
        if lo >= base:
            return  # in-window: the device answers
        q = int(rows["leader_id"][0])
        if not (0 <= q < self.cfg.n_replicas) or q == self.me:
            return
        sb = self.store.base
        if sb >= 0 and lo <= sb:
            # the sweep reaches below our truncation frontier: those
            # redo records are gone — serve the snapshot (pull-path
            # mirror of _host_catchup's push), then commits above it
            self._send_snapshot(q)
            lo = sb + 1
        hi = min(lo + self.cfg.catchup_rows - 1, self.store.committed_prefix())
        if hi < lo:
            self.transport.flush_all()  # the snapshot frames, if any
            return
        frame = self._store_commit_frame(lo, hi, self.snapshot["frontier"])
        if frame is not None:
            self._send_or_redial(q, MsgKind.COMMIT, frame)
        self.transport.flush_all()

    def _become_leader(self) -> None:
        if self.protocol == "mencius":
            return  # no leaders; master be_the_leader promotions no-op
        # complete any deferred host phases first: the election's
        # PREPARE must not overtake the previous tick's still-buffered
        # accepts/commits on the wire
        self._flush_inflight()
        self.state, prep = become_leader(self.cfg, self.state)
        cols = {c: np.asarray(getattr(prep, c)) for c in batches.COLS
                if c != "kind"}
        cols["kind"] = np.asarray(prep.kind)
        frames = batches.rows_to_frames(cols, np.array([True]))
        for kind, frame in frames:
            for q in range(self.cfg.n_replicas):
                if q != self.me:
                    self._send_or_redial(q, kind, frame)
        self.transport.flush_all()
        self._c_elections.inc()
        self.journal.record(EV_ELECTION, subject=self.me,
                            value=self.snapshot["frontier"])
        dlog(f"replica {self.me}: running election")

    # message kinds whose rows address log slots (narrow-view gating
    # reads their slot ranges host-side; everything else only touches
    # scalars or is handled positionally)
    _ADDR_KINDS = (int(MsgKind.ACCEPT), int(MsgKind.COMMIT),
                   int(MsgKind.PREPARE_INST),
                   int(MsgKind.PREPARE_INST_REPLY))
    # kinds that can move crt_inst beyond any row's inst (election
    # traffic reporting peers' log tips) — always take the full step
    _FULL_STEP_KINDS = (int(MsgKind.PREPARE), int(MsgKind.PREPARE_REPLY))

    def _choose_fuse(self, n_rows: int) -> int:
        """Fused substeps for this dispatch: >1 only when the snapshot
        shows follow-up ticks are certainly coming — an exec backlog
        deeper than one exec_batch, or catch-up/broadcast/takeover
        cursors trailing the frontier by a RECOVERY-scale gap. The lag
        threshold is deliberately ~2 client batches (2 x inbox): under
        healthy closed-loop load a follower's reported frontier always
        trails the leader's by about one in-flight batch (it learns
        commitment from the NEXT accept's piggyback), and fusing on
        that steady-state pipeline lag paid 3x compute + duplicate
        catch-up rows per dispatch for follow-ups that had no work
        (measured: first bench attempt this round collapsed to ~2.8k
        ops/s). Blind fusion is a de-optimization; backlog/heal fusion
        is the win."""
        kf = max(1, self.flags.fuse_ticks)
        snap = self.snapshot
        if kf == 1 or "low" not in snap:
            return 1
        if not self.queue.empty():
            # traffic already queued: the next dispatch happens
            # immediately anyway, so its floor is paid regardless —
            # fusing here only delays draining the queue (a k=3 burst
            # blocks inbound acks for 2 extra substeps of compute,
            # which on a compute-bound host stalls the whole pipeline;
            # the first ON-leg A/B measured it as -20% closed-loop)
            return 1
        backlog = snap["frontier"] - snap["executed"]
        trail = snap["frontier"] + 1 - snap["low"]
        lag_floor = max(2 * self.cfg.inbox, self.cfg.catchup_rows)
        if trail > lag_floor:
            return kf  # recovery-scale heal: chunked follow-ups for sure
        if backlog > (kf - 1) * self.cfg.exec_batch:
            # every one of the kf substeps has a full exec_batch of
            # certain work. k is quantized to {1, kf} on purpose: a
            # trailing substep with no work costs a full step of
            # compute (worse than the dispatch it saves on a
            # compute-bound host), and every distinct k is a separate
            # compiled variant — intermediate k values bought little
            # and their first-compile stalls caused client-retry
            # duplicates mid-bench.
            return kf
        return 1

    def _choose_narrow(self, cols, n_rows: int) -> tuple[int, int]:
        """(narrow, off) for this dispatch, or (0, 0) for the full
        step. The narrow view is exact — not an approximation — only
        when every slot the substeps could read or write lands inside
        [window_base+off, window_base+off+narrow): the device-published
        low/high anchors bound the timer-driven paths (exec, retry,
        sweep, catch-up, commit broadcast), the inbox bound covers
        message-driven writes, and proposals extend the tip by at most
        n_rows slots (times R for Mencius's strided ownership)."""
        nw = self.flags.narrow_window
        snap = self.snapshot
        if not nw or nw >= self.cfg.window or "low" not in snap:
            return 0, 0
        if self._narrow_doubt:
            # a post-readback anchor validation failed: run ONE
            # full-width step to recount true anchors from the whole
            # window before trusting the narrow proof again
            self._narrow_doubt = False
            return 0, 0
        base = snap["window_base"]
        low = max(snap["low"], base)
        off = low - base
        if off > self.cfg.window - nw:
            return 0, 0  # view would run off the window; full step slides
        top = base + off + nw  # absolute, exclusive
        stride = self.cfg.n_replicas if self.protocol == "mencius" else 1
        if snap["high"] + n_rows * stride + 1 > top:
            return 0, 0
        if n_rows:
            k = cols["kind"][:n_rows]
            if np.isin(k, self._FULL_STEP_KINDS).any():
                return 0, 0
            inst = cols["inst"][:n_rows]
            lo_req, hi_req = top, low - 1  # empty bounds
            addr = np.isin(k, self._ADDR_KINDS)
            if addr.any():
                lo_req = min(lo_req, int(inst[addr].min()))
                hi_req = max(hi_req, int(inst[addr].max()))
            ar = k == int(MsgKind.ACCEPT_REPLY)
            if ar.any():
                lo_req = min(lo_req, int(inst[ar].min()))
                # run-length acks cover [inst, inst + (count-1)*stride]
                hi_req = max(hi_req, int(
                    (inst[ar] + (np.maximum(cols["cmd_id"][:n_rows][ar], 1)
                                 - 1) * stride).max()))
            sk = k == int(MsgKind.SKIP)
            if sk.any():
                lo_req = min(lo_req, int(
                    cols["last_committed"][:n_rows][sk].min()))
                hi_req = max(hi_req, int(inst[sk].max()))
            if self.protocol == "mencius":
                # COMMIT piggybacks advance crt_inst by the sender's
                # frontier too (models/mencius.py section 6)
                com = k == int(MsgKind.COMMIT)
                if com.any():
                    hi_req = max(hi_req, int(
                        cols["last_committed"][:n_rows][com].max()))
            if lo_req < low or hi_req >= top:
                return 0, 0
        return nw, off

    def _device_tick(self, buf: batches.ColumnBuffer,
                     persist: bool = True, dispatch: bool = True) -> None:
        """One dispatch, as a depth-2 software pipeline: drain this
        tick's inbox and ENQUEUE its jitted step without blocking
        (JAX async dispatch), run the PREVIOUS tick's deferred host
        phases while the device computes, and only then read this
        tick back. Fuse/narrow/idle decisions already consumed the
        previous tick's published snapshot in the serial order, so
        their inputs are unchanged; the step's state input is threaded
        device-side. Host phases are deferred for the NEXT call only
        when follow-up traffic is already queued (see _finish_host) —
        otherwise they complete here, preserving the serial order
        exactly (-nopipeline forces that always)."""
        if DLOG and buf.fill:
            dlog(f"replica {self.me}: tick start fill={buf.fill}")
        clock = self._clock
        with phase(PH_ENQUEUE, clock):
            # host work that grows with the rows of the batch
            with phase(PH_ASSEMBLE, clock):
                cols, n_rows = buf.drain()
                self._skip_span.clear()  # the next batch starts empty
                inbox = MsgBatch(**{c: np.asarray(cols[c])
                                    for c in batches.COLS})
                k = self._choose_fuse(n_rows)
                narrow, off = self._choose_narrow(cols, n_rows)
            view_lo = self.snapshot.get("window_base", 0) + off
            # enqueue: on an async backend the call returns with the
            # outputs still in flight; everything until the np.asarray
            # below overlaps device compute. The span is the call
            # alone: the columns' transfers and the jit dispatch,
            # whose cost the inbox's padded shape sets, not its rows
            with phase(PH_CALL, clock):
                self.state, out_mats_d, exec_mats_d, scals_d = self.step(
                    self.state, inbox, k, narrow, off)
        # the previous tick's host phases, hidden under this compute
        self._flush_inflight(overlapped=True)
        with phase(PH_READBACK, clock):
            self._inflight = rec = self._read_back(
                cols, n_rows, k, narrow, view_lo,
                (out_mats_d, exec_mats_d, scals_d), persist, dispatch)
        rec.enqueue_us = clock.take_us(PH_ENQUEUE)
        rec.readback_us = clock.take_us(PH_READBACK)
        rec.assemble_us = clock.take_us(PH_ASSEMBLE)
        rec.call_us = clock.take_us(PH_CALL)
        rec.enqueue_cpu_us = clock.take_cpu_us(PH_ENQUEUE)
        rec.readback_cpu_us = clock.take_cpu_us(PH_READBACK)
        rec.assemble_cpu_us = clock.take_cpu_us(PH_ASSEMBLE)
        rec.call_cpu_us = clock.take_cpu_us(PH_CALL)
        self._sample_next_row()
        # defer only when the next dispatch is imminent (traffic
        # already queued): its enqueue is what the host phases hide
        # under. With an empty queue the next wakeup may be a full
        # idle interval away — a serial op's reply must not wait for
        # it, so complete in place (this IS the pre-pipeline order).
        if not (self.flags.pipeline and persist and dispatch
                and self._more_queued()):
            self._flush_inflight()

    def _read_back(self, cols: dict, n_rows: int, k: int, narrow: int,
                   view_lo: int, outs: tuple, persist: bool,
                   dispatch: bool) -> _InflightTick:
        """The readback of one dispatch and the publication of what it
        taught — snapshot, counters, leader change, narrow-anchor
        validation, commit stamps — as the tick's host-phase record.
        One ``paxos.tick.readback`` span: the three blocking reads at
        its start are where the host waits for the device."""
        # THREE device reads per dispatch, covering ALL k substeps
        # (stacked outbox/exec/scalar matrices) — see _packed_step;
        # np.asarray blocks until the device finishes: the readback
        out_mats = np.asarray(outs[0])
        exec_mats = np.asarray(outs[1])
        scals = np.asarray(outs[2])
        t_rb_ns = monotonic_ns()  # trace anchor for the dispatch phases
        self._c_dispatches.inc()
        self._c_fused_substeps.inc(k)
        # regime classification, exactly one per dispatch (the flight
        # recorder's kind field uses the same precedence)
        if narrow:
            self._c_narrow_steps.inc()
        elif k > 1:
            self._c_fused_dispatches.inc()
        else:
            self._c_full_steps.inc()
        self._last_dispatch = time.monotonic()
        self._check_kv_load()
        if DLOG and n_rows:
            dlog(f"replica {self.me}: readback k={k} narrow={narrow}")
        mencius = self.protocol == "mencius"
        last = scals[-1]
        self._last_scals = last  # STATS verb surfaces the full vector
        frontier_last = int(last[SCAL_FRONTIER])
        if frontier_last < self.snapshot["frontier"]:
            # the commit frontier is monotonic by construction; going
            # backward means device state was rebuilt/corrupted — make
            # that loudly visible (it presents as a silent wedge)
            dlog(f"replica {self.me}: FRONTIER WENT BACKWARD "
                 f"{self.snapshot['frontier']} -> {frontier_last}")
        # published at readback — strictly before the next tick's
        # fuse/narrow/idle decisions AND before this tick's
        # _host_catchup, exactly as in the serial order
        prev_leader = self.snapshot["leader"]
        self.snapshot = {
            "frontier": frontier_last,
            "window_base": int(last[SCAL_WINDOW_BASE]),
            "crt_inst": int(last[SCAL_CRT_INST]),
            # mencius is leaderless: leader=-1 hints clients any
            # replica serves; prepared=True keeps the re-prepare
            # wedge-guard inert
            "leader": -1 if mencius else int(last[SCAL_LEADER]),
            "prepared": True if mencius else bool(last[SCAL_PREPARED]),
            "executed": int(last[SCAL_EXECUTED]),
            "low": int(last[SCAL_LOW_ANCHOR]),
            "high": int(last[SCAL_HIGH_ANCHOR]),
            "work_pending": bool(last[SCAL_WORK_PENDING]),
        }
        if self.snapshot["leader"] != prev_leader:
            # the device-published leader view moved: an election
            # landed (ours or a peer's) — the journal's leader-change
            # timeline is what the churn detector's evidence joins to
            self.journal.record(EV_LEADER_CHANGE,
                                subject=self.snapshot["leader"],
                                value=frontier_last, aux=prev_leader)
        if narrow:
            # post-readback anchor validation (defense in depth for
            # the pipeline): the choose-time proof said every slot the
            # substeps could touch lies in [view_lo, view_lo+narrow);
            # the device-published post-substep anchors must agree.
            # The low anchor is clamped to each substep's window_base
            # first — a peer lagging BELOW the window legitimately
            # drags low_anchor under the view, but those slots are
            # host-served (_host_catchup), not step-touched, exactly
            # as _choose_narrow's own max(low, base). A violation
            # means a containment assumption broke — count it and
            # recount anchors through one full-width step before
            # trusting the narrow proof again.
            lows = np.maximum(scals[:, SCAL_LOW_ANCHOR],
                              scals[:, SCAL_WINDOW_BASE])
            if (int(lows.min()) < view_lo
                    or int(scals[:, SCAL_HIGH_ANCHOR].max())
                    > view_lo + narrow):
                self._c_narrow_fallbacks.inc()
                self._narrow_doubt = True
                self.journal.record(
                    EV_NARROW_FALLBACK, subject=self.me,
                    value=self._c_narrow_fallbacks.value)
                dlog(f"replica {self.me}: narrow anchor validation "
                     f"FAILED (view [{view_lo}, {view_lo + narrow}), "
                     f"anchors [{int(scals[:, SCAL_LOW_ANCHOR].min())}, "
                     f"{int(scals[:, SCAL_HIGH_ANCHOR].max())}]); next "
                     f"dispatch recounts full-width")
        # read the [R] peer-commit vector NOW, while this state's
        # buffers are still alive (the next enqueue donates them):
        # deferred _host_catchup must see THIS tick's values, and a
        # lazy read later would block on — and read — the next step
        pc = None if mencius else np.asarray(self.state.peer_commits)
        rows_out = int((out_mats[:, 0, :] != 0).sum())  # col 0 = kind
        # a client row that got a slot comes back as this replica's own
        # ACCEPT broadcast at the same row (the alignment _persist uses)
        if n_rows:
            self._c_client_proposals.inc(int((
                (cols["kind"][:n_rows] == int(MsgKind.PROPOSE))
                & (out_mats[0, 0, :n_rows] == int(MsgKind.ACCEPT))).sum()))
        exec_total = int(scals[:, SCAL_EXEC_COUNT].sum())
        self._idle = (n_rows == 0 and rows_out == 0 and exec_total == 0)
        # KV saturation is a correctness failure, not a statistic: a
        # dropped insert belongs to a command that was (or will be)
        # acked, so the state machine silently diverges from the log.
        # The reference's Go map grows without limit (state.go:33-36);
        # a fixed-capacity table must fail-stop instead of serving
        # wrong data. Checked every dispatch, BEFORE this tick's host
        # phases can queue: a fatal tick's replies must never leave.
        dropped = int(last[SCAL_KV_DROPPED])
        if dropped and self.fatal is None:
            self.fatal = (
                f"replica {self.me}: KV table saturated — {dropped} "
                f"write(s) dropped (kv_pow2={self.cfg.kv_pow2} is too "
                f"small for the live key space); failing stop")
            self.journal.record(EV_FATAL, subject=self.me,
                                value=dropped)
            raise FatalReplicaError(self.fatal)
        # coalescer telemetry for the recorder row (schema v7): the
        # rows the ingress front batched into this tick's drain, and
        # the cumulative wakeup kicks. A chased dispatch (overlap_exec)
        # reads 0 — its inbox came from no drain.
        coal = self.coalescer
        coal_occ = coal_wake = 0
        if coal is not None:
            coal_occ, coal.last_occupancy = coal.last_occupancy, 0
            coal_wake = coal._c_wakeups.value
        rec = _InflightTick(
            cols=cols, n_rows=n_rows, out_mats=out_mats,
            exec_mats=exec_mats, scals=scals, k=k,
            kind=(KIND_NARROW if narrow
                  else KIND_FUSED if k > 1 else KIND_FULL),
            persist=persist, dispatch=dispatch, frontier=frontier_last,
            backlog=frontier_last - int(last[SCAL_EXECUTED]),
            rows_out=rows_out, peer_commits=pc, snap=self.snapshot,
            wait_us=self._clock.take_us(PH_WAIT),
            drain_us=self._clock.take_us(PH_DRAIN),
            enqueue_us=0, readback_us=0,  # the caller's, once this ends
            wait_cpu_us=self._clock.take_cpu_us(PH_WAIT),
            drain_cpu_us=self._clock.take_cpu_us(PH_DRAIN),
            sampled=self._clock.sample,
            t_rb_ns=t_rb_ns, coal_occ=coal_occ, coal_wake=coal_wake)
        if self.trace_sink.enabled:
            self._trace_commits(rec)
        return rec

    def _flush_inflight(self, overlapped: bool = False) -> None:
        """Complete the deferred tick's host phases, if any.
        ``overlapped`` marks the stage-2 call between the next tick's
        enqueue and readback — the wall spent there is device-hidden
        and recorded as the row's ``overlap_us``."""
        rec, self._inflight = self._inflight, None
        if rec is not None:
            self._finish_host(rec, overlapped)

    def _finish_host(self, rec: _InflightTick, overlapped: bool) -> None:
        """The host side of one dispatched tick: persist -> dispatch ->
        reply -> catch-up, each as ONE vectorized pass over the stacked
        [k, ...] substep matrices (the old per-substep Python replay
        paid k iterations of mask/extract work per dispatch). Ordering
        contract preserved: the store flush (fsync under -durable)
        happens before any buffered reply frame reaches a socket
        (flush_all is last)."""
        clock = self._clock
        # this row's host phases are measured as its dispatch phases
        # were, whatever the row being gathered meanwhile is
        clock.sample, resume = rec.sampled, clock.sample
        cols, n_rows, k = rec.cols, rec.n_rows, rec.k
        out_mats, exec_mats, scals = rec.out_mats, rec.exec_mats, rec.scals
        ncols = len(batches.COLS)
        if rec.persist:
            # always maintained (in-memory mirror feeds beyond-window
            # catch-up); -durable additionally fsyncs before replies
            with phase(PH_PERSIST, clock):
                out0 = {c: out_mats[0][j]
                        for j, c in enumerate(batches.COLS)}
                acked0 = out_mats[0][ncols + 1].astype(bool)
                wrote = self._persist(cols, n_rows, out0, acked0,
                                      int(scals[0][SCAL_FRONTIER]))
                if k > 1:
                    # substeps 1..k-1 ran empty inboxes, so every
                    # persistable row of theirs is an outbox tail row
                    # (retry/noop/catch-up ACCEPTs + mencius SKIPs):
                    # one concatenated pass over all of them at once,
                    # substep-major order preserved by the reshape
                    big = {c: out_mats[1:, j, :].reshape(-1)
                           for j, c in enumerate(batches.COLS)}
                    wrote |= self._persist(cols, 0, big,
                                           np.zeros(0, bool), rec.frontier)
                if wrote:
                    # ONE store flush (fsync under -durable, its own
                    # paxos.tick.fsync span inside this one) covers
                    # all k substeps: outbound frames only hit the
                    # sockets at flush_all below (FrameWriter buffers,
                    # wire/codec.py), so the fsync-before-acks-leave
                    # ordering holds without paying k fsyncs per fused
                    # dispatch
                    self.store.flush()
        if rec.dispatch:
            with phase(PH_EGRESS, clock):
                if rec.rows_out:
                    # the reshapes COPY (strided slices), so build
                    # them only when there are live rows to scatter —
                    # backlog-drain ticks execute commands without
                    # emitting any
                    with phase(PH_PEERS, clock):
                        flat = {c: out_mats[:, j, :].reshape(-1)
                                for j, c in enumerate(batches.COLS)}
                        self._dispatch(flat,
                                       out_mats[:, ncols, :].reshape(-1))
            with phase(PH_REPLY, clock):
                self._reply_stacked(exec_mats, scals, k, rec.frontier)
            with phase(PH_EGRESS, clock):
                # egress's SELF time (it less peers and flush)
                self._host_catchup(rec.peer_commits, rec.snap)
                with phase(PH_FLUSH, clock):
                    self.transport.flush_all()
        # flight-recorder row + latency histograms: the per-phase wall
        # decomposition for THIS dispatch, wall-honest under fusion
        # (one row per dispatch, carrying k — a fused burst is one
        # wall tick; consumers divide by k for per-substep cost).
        # overlap_us = this tick's host-phase wall executed while the
        # NEXT dispatch was in flight on the device (0 when serial).
        persist_us = clock.take_us(PH_PERSIST)
        egress_us = clock.take_us(PH_EGRESS)
        reply_us = clock.take_us(PH_REPLY)
        host_us = persist_us + egress_us + reply_us
        self._h_tick.observe((rec.drain_us + rec.enqueue_us
                              + rec.readback_us + host_us) / 1e3)
        persist_cpu_us = clock.take_cpu_us(PH_PERSIST)
        egress_cpu_us = clock.take_cpu_us(PH_EGRESS)
        reply_cpu_us = clock.take_cpu_us(PH_REPLY)
        # the seven tiling takes of THIS row are done (the dispatch
        # phases' at its readback): a sampled row's cpu_us is their sum
        cpu_us = clock.cpu_us(rec.sampled)
        self._c_proto_cpu.inc(cpu_us)
        clock.sample = resume
        if self.recorder is not None:
            flushed = self.store.flushed_bytes
            fsync_bytes, self._flushed_seen = (
                flushed - self._flushed_seen, flushed)
            self._g_threads.set(threading.active_count())
            self.recorder.record(
                monotonic_ns(), rec.kind, k, n_rows, rec.rows_out,
                rec.frontier, rec.backlog, rec.drain_us, rec.enqueue_us,
                rec.readback_us, host_us if overlapped else 0,
                persist_us, egress_us, reply_us, rec.t_rb_ns,
                chaos_faults=self.transport.chaos_faults_total(),
                coal_occ=rec.coal_occ, coal_wake=rec.coal_wake,
                wait_us=rec.wait_us, fsync_us=clock.take_us(PH_FSYNC),
                fsync_bytes=fsync_bytes, cpu_us=cpu_us,
                assemble_us=rec.assemble_us, call_us=rec.call_us,
                peer_send_us=clock.take_us(PH_PEERS),
                flush_us=clock.take_us(PH_FLUSH),
                wait_cpu_us=rec.wait_cpu_us,
                drain_cpu_us=rec.drain_cpu_us,
                enqueue_cpu_us=rec.enqueue_cpu_us,
                readback_cpu_us=rec.readback_cpu_us,
                persist_cpu_us=persist_cpu_us,
                fsync_cpu_us=clock.take_cpu_us(PH_FSYNC),
                dispatch_cpu_us=egress_cpu_us, reply_cpu_us=reply_cpu_us,
                assemble_cpu_us=rec.assemble_cpu_us,
                call_cpu_us=rec.call_cpu_us,
                peer_send_cpu_us=clock.take_cpu_us(PH_PEERS),
                flush_cpu_us=clock.take_cpu_us(PH_FLUSH),
                cpu_sampled=rec.sampled)

    # -- paxtrace: slot assignment + commit stamps (protocol thread) --

    def _trace_commits(self, rec: _InflightTick) -> None:
        """Two paxtrace duties per dispatch (three under mencius: the
        ST_OWN_COMMIT stamp between them), all O(sampled):

        1. learn the log slot of every SAMPLED proposal this tick
           admitted — the kernel's ACCEPT broadcast at outbox row i
           carries the slot it assigned to inbox PROPOSE row i (the
           same row alignment ``_persist`` relies on);
        2. stamp ST_COMMIT for tracked slots the tick's frontier just
           covered, at the tick's readback time (``t_rb_ns`` — the
           moment the host LEARNED the commit; the device rounds in
           between are the span).

        The tracked set is a min-heap keyed on slot, NOT a dict: slots
        can sit above the contiguous frontier for many dispatches
        (out-of-order exec, re-proposals), and a full per-dispatch
        scan of every tracked slot is protocol-thread time the
        blocking-frontier protocols cannot spare under load.
        """
        sink = self.trace_sink
        n = rec.n_rows
        if n:
            ik = rec.cols["kind"][:n]
            pm = ik == int(MsgKind.PROPOSE)
            if pm.any():
                ids = rec.cols["cmd_id"][:n]
                out_kind = rec.out_mats[0, 0, :n]  # col 0 = kind
                sm = pm & sink.sampled(ids) \
                    & (out_kind == int(MsgKind.ACCEPT))
                if sm.any():
                    out_inst = rec.out_mats[0, 3, :n]  # col 3 = inst
                    ccol = rec.cols["client_id"][:n]
                    for i in np.nonzero(sm)[0]:
                        # linearizable READs ride the log as PROPOSE
                        # rows too — their chains never complete (no
                        # drain/exec spans by design), so a commit
                        # stamp would only churn the ring
                        if self._pending.get(
                                (int(ccol[i]), int(ids[i]))) \
                                == MsgKind.READ_REPLY:
                            continue
                        heapq.heappush(self._trace_slots,
                                       (int(out_inst[i]), int(ids[i])))
                        if self.protocol == "mencius":
                            self._trace_own[int(out_inst[i])] = int(ids[i])
        own = self._trace_own
        if own:
            # mencius: the owner's COMMIT row of a tracked slot left
            # the device in this dispatch — its own quorum is settled;
            # what remains until ST_COMMIT below is the wait for the
            # other owners' slots under it (the merge_wait)
            kinds = rec.out_mats[:, 0, :]
            sent = rec.out_mats[:, 3, :][kinds == int(MsgKind.COMMIT)]
            ring = sink.ring()
            for s in own.keys() & set(sent.tolist()):
                ring.record(trace_id_for(own.pop(s)), ST_OWN_COMMIT,
                            rec.t_rb_ns, rec.t_rb_ns, s)
        if self._trace_slots and self._trace_slots[0][0] <= rec.frontier:
            ring = sink.ring()
            while self._trace_slots and \
                    self._trace_slots[0][0] <= rec.frontier:
                s, cmd = heapq.heappop(self._trace_slots)
                own.pop(s, None)  # passed before its row left: no stamp
                ring.record(trace_id_for(cmd), ST_COMMIT,
                            rec.t_rb_ns, rec.t_rb_ns, s)

    # -- durability: reconstruct accepted slots from (inbox, outbox) --

    def _persist(self, in_cols, n_rows, out_cols, acked,
                 frontier: int) -> bool:
        """Accepted slots are reconstructed host-side from the inbox
        plus the kernel's outputs (``frontier`` is this substep's
        committed_upto, read from the packed scalar vector instead of
        a fresh per-tick device read). Returns whether anything was
        appended; the CALLER flushes the store once per dispatch,
        before any buffered ack/reply frame reaches a socket:

        * follower acks: the kernel's per-inbox-row ``acked`` mask
          (Outbox.acked — outbox ACCEPT_REPLY rows are run-length
          compressed and no longer align 1:1 with inbox rows) -> slot
          from inbox ACCEPT row i
        * leader self-accepts: out ACCEPT broadcast at i -> cmd from
          inbox PROPOSE row i (command rows stay row-aligned)
        * commits applied: inbox COMMIT rows
        * retry/noop rows (appended tail segments): out ACCEPT rows
          beyond the inbox range carry full commands
        """
        n = n_rows
        ik = in_cols["kind"][:n]
        ok_acc = acked[:n] & (ik == int(MsgKind.ACCEPT))
        lead_acc = out_cols["kind"][:n] == int(MsgKind.ACCEPT)
        com = ik == int(MsgKind.COMMIT)
        recs = []
        if ok_acc.any() or com.any():
            m = ok_acc | com
            # dedup persists of already-committed slots: a heal sweep
            # delivers R-1 copies of every slot (each peer answers
            # PREPARE_INST with the same COMMIT row, often all in one
            # tick's inbox), and re-ACCEPTs of committed slots re-ack;
            # commitment is final, so re-appending only amplifies log
            # growth + fsync volume. Drop (a) rows the store already
            # holds committed (frontier or explicit record, vectorized),
            # (b) all but the first COMMIT row per inst in this batch.
            idx = np.nonzero(m)[0]
            dup = self.store.is_committed(in_cols["inst"][:n][idx])
            m[idx[dup]] = False
            com = com & m
            cidx = np.nonzero(com)[0]
            if len(cidx) > 1:
                _, first = np.unique(in_cols["inst"][:n][cidx],
                                     return_index=True)
                drop = np.ones(len(cidx), bool)
                drop[first] = False
                m[cidx[drop]] = False
                com = com & m
            recs.append((in_cols["inst"][:n][m], in_cols["ballot"][:n][m],
                         np.where(com[m], COMMITTED, ACCEPTED),
                         in_cols["op"][:n][m],
                         join_i64(in_cols["key_hi"][:n][m], in_cols["key_lo"][:n][m]),
                         join_i64(in_cols["val_hi"][:n][m], in_cols["val_lo"][:n][m]),
                         in_cols["cmd_id"][:n][m], in_cols["client_id"][:n][m]))
        if lead_acc.any():
            m = lead_acc
            recs.append((out_cols["inst"][:n][m], out_cols["ballot"][:n][m],
                         np.full(m.sum(), ACCEPTED),
                         out_cols["op"][:n][m],
                         join_i64(out_cols["key_hi"][:n][m], out_cols["key_lo"][:n][m]),
                         join_i64(out_cols["val_hi"][:n][m], out_cols["val_lo"][:n][m]),
                         out_cols["cmd_id"][:n][m], out_cols["client_id"][:n][m]))
        # appended tail segments (recovery/frontier/catchup/retry rows).
        # Catch-up rows (7c) re-ship slots this leader already holds
        # committed-durable — skip re-appending those (same dedup as
        # above, leader-side); retry rows for uncommitted slots still
        # persist.
        t = slice(n, None)
        tail_acc = (out_cols["kind"][t] == int(MsgKind.ACCEPT)) \
            & ~self.store.is_committed(out_cols["inst"][t])
        if tail_acc.any():
            m = tail_acc
            recs.append((out_cols["inst"][t][m], out_cols["ballot"][t][m],
                         np.full(m.sum(), ACCEPTED),
                         out_cols["op"][t][m],
                         join_i64(out_cols["key_hi"][t][m], out_cols["key_lo"][t][m]),
                         join_i64(out_cols["val_hi"][t][m], out_cols["val_lo"][t][m]),
                         out_cols["cmd_id"][t][m], out_cols["client_id"][t][m]))
        if self.protocol == "mencius":
            # SKIP ranges commit no-ops for the ceder's owned slots
            # (models/mencius.py steps 3-4); without records for them
            # the committed prefix would have permanent holes on replay
            from minpaxos_tpu.wire.messages import Op as _Op

            for cols_, hi in ((in_cols, n), (out_cols, None)):
                ks = cols_["kind"][:hi]
                for j in np.nonzero(ks == int(MsgKind.SKIP))[0]:
                    owner = int(cols_["src"][:hi][j])
                    start = int(cols_["last_committed"][:hi][j])
                    end = int(cols_["inst"][:hi][j])
                    if end < start:
                        continue
                    slots = np.arange(start, end + 1, dtype=np.int64)
                    slots = slots[slots % self.cfg.n_replicas == owner]
                    slots = slots[~self.store.is_committed(slots)]
                    if len(slots):
                        z = np.zeros(len(slots), np.int64)
                        recs.append((slots.astype(np.int32),
                                     z.astype(np.int32),
                                     np.full(len(slots), COMMITTED),
                                     np.full(len(slots), int(_Op.NONE)),
                                     z, z, z.astype(np.int32),
                                     np.full(len(slots), -1, np.int32)))
        wrote = False
        for inst, ballot, status, op, key, val, cmd, cli in recs:
            if len(inst):
                self.store.append_slots(inst, ballot, status, op, key, val,
                                        cmd, cli)
                wrote = True
        if frontier > self.store.frontier:
            self.store.append_frontier(frontier)
            wrote = True
        return wrote

    # -- outbox dispatch --

    def _quorum_targets(self) -> list[int]:
        """Thrifty: accepts go to floor(N/2) peers only
        (paxos.go:278-281); with beacons on, the lowest-RTT peers
        (UpdatePreferredPeerOrder, genericsmr.go:554-580)."""
        peers = [q for q in range(self.cfg.n_replicas) if q != self.me]
        if self.flags.beacon:
            peers.sort(key=lambda q: self.rtt_ewma[q])
        return peers[: self.cfg.n_replicas // 2]

    def _send_or_redial(self, q, kind, frame) -> None:
        if not self.transport.send_peer(q, kind, frame):
            if self.transport.dial_peer(q):
                self.transport.send_peer(q, kind, frame)

    def _dispatch(self, out_cols, dst) -> None:
        kinds = out_cols["kind"]
        live = kinds != 0
        if not live.any():
            return
        if DLOG:
            dlog(f"replica {self.me}: dispatch "
                 f"{np.bincount(kinds[live]).nonzero()[0].tolist()}")
        thrifty_q = self._quorum_targets() if self.flags.thrifty else None
        for q in range(self.cfg.n_replicas):
            if q == self.me:
                continue
            mask = live & ((dst == q) | (dst == -1))
            if thrifty_q is not None and q not in thrifty_q:
                # thrifty drops broadcast ACCEPTs for non-quorum peers;
                # unicast rows (their catch-up) still flow
                mask = mask & ~((dst == -1) & (kinds == int(MsgKind.ACCEPT)))
            if not mask.any():
                continue
            for kind, frame in batches.rows_to_frames(out_cols, mask):
                self._send_or_redial(q, kind, frame)
        # client-bound rejections (dst == -2): ProposeReplyTS{FALSE,
        # Leader} so clients re-route (bareminpaxos.go:618-625)
        rej = live & (dst == -2) & (kinds == int(MsgKind.PROPOSE_REPLY))
        if rej.any():
            self._c_rejected.inc(int(rej.sum()))
            leader_hint = out_cols["ballot"][rej]
            cids = out_cols["client_id"][rej]
            cmds = out_cols["cmd_id"][rej]
            for cid in np.unique(cids):
                m = cids == cid
                frame = make_batch(MsgKind.PROPOSE_REPLY, ok=0,
                                   cmd_id=cmds[m], val=0,
                                   timestamp=monotonic_ns(),
                                   leader=leader_hint[m].astype(np.int8))
                self.transport.send_client(int(cid), MsgKind.PROPOSE_REPLY,
                                           frame)
                for c in cmds[m]:
                    self._pending.pop((int(cid), int(c)), None)

    # -- execution replies (ReplyProposeTS, genericsmr.go:529) --

    def _reply_stacked(self, exec_mats: np.ndarray, scals: np.ndarray,
                       k: int, frontier: int) -> None:
        """Execution replies for ALL k substeps in one pass: the
        stacked [k, 6, E] exec matrices concatenate (substep-major, so
        per-connection reply order matches the k-iteration replay this
        replaces) and the grouping/pending bookkeeping runs once."""
        counts = scals[:, SCAL_EXEC_COUNT]
        total = int(counts.sum())
        self._c_executed.inc(total)
        self._g_committed.set(frontier + 1)
        if total:
            noops = sum(int((exec_mats[i][3][:int(c)] == int(Op.NONE)).sum())
                        for i, c in enumerate(counts) if c > 0)  # row 3 = op
            self._c_noop_slots.inc(noops)
            self._c_command_slots.inc(total - noops)
        if total == 0 or not self.flags.dreply:
            return
        if DLOG:
            dlog(f"replica {self.me}: reply n={total}")
        live = [i for i in range(k) if counts[i] > 0]
        cids = np.concatenate(
            [exec_mats[i][5][:int(counts[i])] for i in live])
        cmds = np.concatenate(
            [exec_mats[i][4][:int(counts[i])] for i in live])
        vals = join_i64(
            np.concatenate([exec_mats[i][0][:int(counts[i])]
                            for i in live]),
            np.concatenate([exec_mats[i][1][:int(counts[i])]
                            for i in live]))
        # group-by client connection: ONE frame (and one socket write)
        # per (conn, kind) instead of a frame per executed command —
        # the reply path must stay invisible next to the device step
        # at bench load. No-op fills (cid < 0) are dropped vectorized.
        writes: dict[int, tuple[list, list]] = {}
        reads: dict[int, tuple[list, list]] = {}
        sink = self.trace_sink
        tracing = sink.enabled
        traced: list[int] = []
        t_x0 = monotonic_ns() if tracing else 0
        # ONE vectorized sampling hash for the whole pass (the drain-
        # path discipline): a scalar per-command hash here measured
        # ~18x slower per 512-command batch, paid on the protocol
        # thread for every write regardless of sample rate
        smask = sink.sampled(cmds) if tracing else None
        for i in np.nonzero(cids >= 0)[0]:
            key = (int(cids[i]), int(cmds[i]))
            want = self._pending.pop(key, None)
            if want is None:
                continue  # not proposed on this conn (or already replied)
            if tracing and want != MsgKind.READ_REPLY and smask[i]:
                # writes only: reads never get DRAIN/COMMIT spans, so
                # an exec/reply stamp for them could never complete a
                # chain — it would just churn the fixed rings
                traced.append(key[1])
            book = reads if want == MsgKind.READ_REPLY else writes
            cs_, vs_ = book.setdefault(key[0], ([], []))
            cs_.append(key[1])
            vs_.append(int(vals[i]))
        ts = monotonic_ns()
        for conn, (cs_, vs_) in writes.items():
            frame = make_batch(MsgKind.PROPOSE_REPLY, ok=1,
                               cmd_id=np.asarray(cs_, np.int32),
                               val=np.asarray(vs_, np.int64),
                               timestamp=ts, leader=np.int8(self.me))
            self.transport.send_client(conn, MsgKind.PROPOSE_REPLY, frame)
        for conn, (cs_, vs_) in reads.items():
            frame = make_batch(MsgKind.READ_REPLY,
                               cmd_id=np.asarray(cs_, np.int32),
                               val=np.asarray(vs_, np.int64))
            self.transport.send_client(conn, MsgKind.READ_REPLY, frame)
        if traced:
            # one exec stamp (when the reply pass picked the command
            # up — commit -> here is the exec-backlog wait; aux = the
            # dispatch count, closing the drain-aux round correlation)
            # and one reply-serialization span per sampled command.
            # The span ends at ``ts`` — taken BEFORE the send loop: a
            # same-host client can receive a frame before this code
            # runs again, and a reply_ser end stamped after the sends
            # would put reply_recv BEFORE it (negative transport_out,
            # chain dropped as impossible under exactly the load the
            # tail table exists to explain).
            ring = sink.ring()
            disp = self._c_dispatches.value
            for cmd in traced:
                tid = trace_id_for(cmd)
                ring.record(tid, ST_EXEC, t_x0, t_x0, disp)
                ring.record(tid, ST_REPLY_SER, t_x0, ts, cmd)

    # -- beyond-window catch-up from the durable log --

    def _host_catchup(self, pc: np.ndarray | None, snap: dict) -> None:
        """A peer lagging behind window_base can't be healed by device
        catch-up rows (they slid out); serve it from the stable store's
        in-memory mirror instead — the runtime's replacement for the
        reference replaying its whole file to the new process.

        ``pc``/``snap`` are the tick's OWN peer-commit vector and
        published snapshot, captured at its readback: under the
        pipeline this runs after the next step was enqueued, when
        ``self.state``'s buffers are already donated — a live read
        here would block on (and read) the wrong tick."""
        if self.protocol == "mencius" or pc is None:
            # leaderless: there is no leader to push catch-up. Healing
            # is PULL-based instead — the laggard's takeover sweep
            # (kernel) plus peers' store-served COMMIT answers to
            # beyond-window PREPARE_INSTs (_mencius_store_answer).
            return
        if not snap["prepared"] or snap["leader"] != self.me:
            return
        base = snap["window_base"]
        fr = snap["frontier"]
        sb = self.store.base
        for q in range(self.cfg.n_replicas):
            if q == self.me or pc[q] + 1 >= base:
                continue
            if sb >= 0 and pc[q] < sb:
                # the peer needs slots BELOW our truncation frontier —
                # those redo records no longer exist anywhere on this
                # replica's disk. Ship the retained snapshot instead
                # (SNAP_META + SNAP_ROWS, paced); the live suffix
                # above it follows through this same path once the
                # peer's reported frontier clears the snapshot.
                self._send_snapshot(q)
                continue
            frame = self._store_commit_frame(
                int(pc[q]) + 1, min(int(pc[q]) + 256, base - 1), fr)
            if frame is not None:
                self._send_or_redial(q, MsgKind.COMMIT, frame)

    # minimum seconds between snapshot re-pushes to one peer: a
    # transfer already in flight must not be re-sent every tick, and a
    # peer that installed it advances its reported frontier well
    # before this expires
    _SNAP_RESEND_S = 2.0

    def _send_snapshot(self, q: int) -> None:
        """Push the newest retained snapshot to peer q: one SNAP_META
        announcement, then its live pairs as SNAP_ROWS frames. Every
        row repeats the snapshot frontier, so the receiver can never
        splice two transfers; completeness is count-checked before
        install (_snap_rx_install)."""
        now = time.monotonic()
        if now - self._snap_sent_s.get(q, -1e9) < self._SNAP_RESEND_S:
            return
        fr = self.store.snap_frontier
        pairs = self.store.snapshot_pairs
        if fr < 0:
            return
        self._snap_sent_s[q] = now
        self._snap_seq += 1
        meta = make_batch(MsgKind.SNAP_META, leader_id=self.me,
                          frontier=fr, count=len(pairs),
                          seq=self._snap_seq)
        self._send_or_redial(q, MsgKind.SNAP_META, meta)
        for lo in range(0, len(pairs), 4096):
            ch = pairs[lo:lo + 4096]
            rows = make_batch(MsgKind.SNAP_ROWS, frontier=fr,
                              key=np.ascontiguousarray(ch["key"]),
                              val=np.ascontiguousarray(ch["val"]))
            self._send_or_redial(q, MsgKind.SNAP_ROWS, rows)
        dlog(f"replica {self.me}: pushed snapshot@{fr} "
             f"({len(pairs)} pairs) to replica {q}")

    def _snap_rx_install(self) -> None:
        """Install a COMPLETE received snapshot that is ahead of our
        own executed frontier (protocol thread, called from _drain).
        Install = the KV pairs into the device table + every cursor to
        frontier+1 (_install_snapshot_pairs), then the snapshot into
        OUR OWN stable store — a restart of this replica must replay
        from it, not from slot 0 of a log it never held."""
        for fr in sorted(self._snap_rx):
            st = self._snap_rx[fr]
            if sum(len(r) for r in st["rows"]) < st["count"]:
                continue
            del self._snap_rx[fr]
            if fr <= int(self.snapshot.get("executed", -1)):
                continue  # stale by the time it completed
            t0 = time.perf_counter()
            self._flush_inflight()
            pairs = (np.concatenate(st["rows"]) if st["rows"]
                     else empty_batch(MsgKind.SNAP_ROWS))
            self._install_snapshot_pairs(pairs, fr)
            self.store.take_snapshot(
                np.ascontiguousarray(pairs["key"]),
                np.ascontiguousarray(pairs["val"]), fr,
                wall_ns=time.time_ns())
            # publish before the next dispatch: fuse/narrow/idle
            # decisions and the catch-up sender must see the new
            # frontier, exactly as a readback would publish it
            self.snapshot = dict(
                self.snapshot, frontier=fr, executed=fr,
                window_base=fr + 1,
                crt_inst=max(int(self.snapshot.get("crt_inst", 0)),
                             fr + 1),
                work_pending=True)
            self.journal.record(
                EV_RECOVERY, subject=self.me, value=fr,
                aux=int((time.perf_counter() - t0) * 1e3))
            dlog(f"replica {self.me}: installed snapshot@{fr} "
                 f"({len(pairs)} pairs) from replica {st['src']}")
        # drop buffers that can no longer install (at/below our own
        # frontier): a dead transfer must not pin its rows forever
        done = int(self.snapshot.get("executed", -1))
        for fr in [f for f in self._snap_rx if f <= done]:
            del self._snap_rx[fr]
