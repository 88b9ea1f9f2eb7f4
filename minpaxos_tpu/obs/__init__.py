"""paxmon — observability for the TPU consensus runtime.

The reference repo's only runtime evidence is scattered ``log.Printf``
calls; this package is the layer the ROADMAP's production north star
presupposes: a **typed metrics registry** (counters / gauges /
fixed-bucket histograms, thread-safe snapshots, zero allocation on the
protocol thread's hot path) and a **per-tick flight recorder** (a
fixed-size numpy ring logging dispatch kind, fused k, row counts,
frontier, exec backlog and the per-phase wall decomposition —
drain / enqueue / readback / persist / dispatch / reply, plus the
pipeline's device-hidden host wall as overlap_us), exportable as
Chrome trace-event JSON loadable in Perfetto.

Siblings in this package: ``obs/trace.py`` (paxtrace — sampled
per-command stage spans) and ``obs/watch.py`` (paxwatch — the
cluster-event journal, health-sample retention, and SLO/anomaly
detectors).

Deliberately dependency-light (stdlib + numpy, no jax): the control
plane, ``tools/paxtop.py``, ``tools/paxwatch.py`` and the CI smoke
(``tools/obs_smoke.py``) must all run cold without a backend init.

Consumers:

* ``runtime/replica.py`` — owns one registry + recorder per replica,
  serves them over the control socket (``STATS`` / ``TRACE`` verbs).
* ``runtime/master.py`` — fans the verbs out cluster-wide.
* ``tools/paxtop.py`` — the live terminal view.
* a harness that holds the servers in its own process (the
  benchmark's ``served`` runner) — ``process_collection()`` below;
  one that holds a sharded pod (its ``pod`` runner) —
  ``process_pods()``.

See OBSERVABILITY.md at the repo root for the metric catalogue and
the trace field glossary.
"""

import collections

import numpy as np

from minpaxos_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    TICK_MS_BUCKETS,
)
from minpaxos_tpu.obs.recorder import (
    DEVICE_PID,
    TRACE_PID,
    WATCH_PID,
    FlightRecorder,
    PhaseClock,
    KIND_FULL,
    KIND_FUSED,
    KIND_IDLE_SKIP,
    KIND_NAMES,
    KIND_NARROW,
    N_TEL_FIELDS,
    SCHEMA_VERSION,
    TEL_FIELD_NAMES,
    chrome_trace,
    device_round_events,
    phase,
    telemetry_valid_rows,
    validate_chrome_trace,
)
from minpaxos_tpu.obs.trace import (
    DECOMP_STAGES,
    STAGE_NAMES,
    SpanRing,
    TraceSink,
    align_collections,
    analyze_collections,
    format_stage_table,
    is_sampled,
    protocol_ring_capacity,
    sampled_mask,
    span_chains,
    span_events,
    stage_decomposition,
    stage_table,
    trace_id_for,
)
from minpaxos_tpu.obs.watch import (
    DETECTOR_NAMES,
    EVENT_FIELD_NAMES,
    EVENT_NAMES,
    EventJournal,
    EventRing,
    HealthSeries,
    HealthWatcher,
    SLO,
    align_event_collections,
    event_chrome_events,
    flatten_cluster_stats,
)

#: the newest replicas of this process, each ``(replica id, registry,
#: recorder or None, trace sink)``: registered at construction and kept
#: after ``stop()``, so a harness in the same process can read what a
#: run left behind without reaching into a server. Bounded: a process
#: that builds clusters all day (the test suite) keeps sixteen.
_PROCESS_REPLICAS: collections.deque = collections.deque(maxlen=16)


def register_replica(replica: int, registry: MetricsRegistry,
                     recorder: FlightRecorder | None,
                     trace_sink: TraceSink) -> None:
    _PROCESS_REPLICAS.append((replica, registry, recorder, trace_sink))


def process_collection() -> list[dict]:
    """Per registered replica, oldest first: ``replica``, ``rows`` (the
    recorder's ``snapshot()``; None under -norecorder) with
    ``rows_total`` ever recorded and the ring's ``rows_capacity``,
    ``spans`` (``TraceSink.collect()``) and ``metrics`` (the registry's
    ``snapshot()``). Everything is a copy taken now."""
    out = []
    for replica, registry, recorder, sink in list(_PROCESS_REPLICAS):
        out.append({
            "replica": replica,
            "rows": None if recorder is None else recorder.snapshot(),
            "rows_total": 0 if recorder is None else recorder.total,
            "rows_capacity": 0 if recorder is None else recorder.capacity,
            "spans": sink.collect(),
            "metrics": registry.snapshot()})
    return out


#: the newest sharded pods of this process (parallel/sharded.py
#: ``ShardedCluster``), each the dict it registered at construction:
#: its shape and, under ``tiers`` and ``gates``, the resident loop's
#: tier and recovery-gate counts as of the pod's last post-window read
#: (None before one), with a multi-owner pod's ``command_commits`` and
#: ``noop_slots`` of the same rounds (None for a single-leader pod).
#: Apart from ``_PROCESS_REPLICAS``, whose entries are replicas with
#: recorder rows.
_PROCESS_PODS: collections.deque = collections.deque(maxlen=16)


def register_pod(info: dict) -> dict:
    """Keep ``info`` for ``process_pods()``; the pod updates it in
    place when it reads its device-resident counts back."""
    _PROCESS_PODS.append(info)
    return info


def process_pods() -> list[dict]:
    """Per registered pod, oldest first: ``protocol``, ``n_shards``,
    ``n_replicas``, ``inbox``, ``working_capacity``, ``tiers``
    (``kernel_small_rounds``, ``route_small_rounds``, ``rounds`` from
    the last ``begin_resident`` to the last post-window read, or None),
    ``gates`` (over the same rounds, per recovery section of the step,
    by its ``px.*`` scope, the rounds in which its gate was OPEN:
    ``{"px.retry": 0}``, of ``tiers["rounds"]``; or None) and, over the
    same rounds, a multi-owner (Mencius) pod's
    ``command_commits`` and ``noop_slots`` (else None) and a
    single-leader pod's recovery counts (else None): ``round_gates``
    (as ``gates``, for the sections of the round itself:
    ``{"px.state_transfer": 0}``), ``state_transfers`` (installs of a
    leader's executed state in a follower its window could no longer
    heal), ``state_transfer_bytes`` (what those copied) and
    ``lagging_rounds`` (rounds at whose end a live replica's frontier
    trailed its leader's by more than two rounds' proposals). And the
    resident loop's HOST side since the last ``begin_resident``:
    ``dispatches`` it made and, for each of the newest 4,096 of them,
    oldest first, ``dispatch_ns`` (the host interval of span
    ``paxos.pod.dispatch``: the host-made scalars and the jitted call,
    until it returns) and ``readback_ns`` (span ``paxos.pod.readback``:
    the host blocked on the two scalars). Copies taken now."""
    return [dict(p, tiers=p["tiers"] and dict(p["tiers"]),
                 gates=p["gates"] and dict(p["gates"]),
                 round_gates=p["round_gates"]
                 and dict(p["round_gates"]),
                 dispatch_ns=_ring_oldest_first(p, "dispatch_ns"),
                 readback_ns=_ring_oldest_first(p, "readback_ns"))
            for p in list(_PROCESS_PODS)]


def _ring_oldest_first(pod: dict, key: str):
    """A copy of what a pod's host ring holds, oldest dispatch first
    (None for an entry registered without one)."""
    ring = pod.get(key)
    if ring is None:
        return None
    n = pod["dispatches"]
    if n <= len(ring):
        return ring[:n].copy()
    at = n % len(ring)
    return np.concatenate([ring[at:], ring[:at]])


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "process_collection", "register_replica", "process_pods",
    "register_pod", "PhaseClock", "phase",
    "protocol_ring_capacity",
    "TICK_MS_BUCKETS", "FlightRecorder", "KIND_FULL", "KIND_FUSED",
    "KIND_NARROW", "KIND_IDLE_SKIP", "KIND_NAMES", "SCHEMA_VERSION",
    "DEVICE_PID", "TRACE_PID", "N_TEL_FIELDS", "TEL_FIELD_NAMES",
    "chrome_trace", "device_round_events", "telemetry_valid_rows",
    "validate_chrome_trace",
    "DECOMP_STAGES", "STAGE_NAMES", "SpanRing", "TraceSink",
    "align_collections", "analyze_collections", "format_stage_table",
    "is_sampled",
    "sampled_mask", "span_chains", "span_events",
    "stage_decomposition", "stage_table", "trace_id_for",
    "WATCH_PID", "DETECTOR_NAMES", "EVENT_FIELD_NAMES", "EVENT_NAMES",
    "EventJournal", "EventRing", "HealthSeries", "HealthWatcher",
    "SLO", "align_event_collections", "event_chrome_events",
    "flatten_cluster_stats",
]
