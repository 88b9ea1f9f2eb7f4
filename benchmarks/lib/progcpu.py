"""Who had the CPU, from what the program recorded about itself: the
recorder's per-phase CPU fields (schema v9: ``x_cpu_us`` beside every
wall field ``x_us``), the registry's ``ingress_cpu_us`` / ``proto_cpu_us``
counters, and the pod loop's host ring in ``obs.process_pods()``.

The chip host's thread clock moves in 10 ms steps (``progobs.
tick_cpu_share_pct``), so every CPU reading here is a RATIO OF SUMS over
the leader's loaded dispatches, never a median of rows; and a read of
it costs 6-52 us there, so the program measures the per-phase fields of
one row in eight and marks it ``cpu_sampled``. Against a
program without the field, the counter or the ring every function
returns None: the metric is left out.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import progobs

#: fewer dispatches than this in the pod's ring is nothing to read
MIN_POD_DISPATCHES = 8
#: fewer loaded dispatches with measured CPU times than this give no share
MIN_CPU_ROWS = 30


def phase_cpu_share_pct(wall_field: str, coll=None) -> float | None:
    """100 x the protocol thread's CPU time inside one phase over that
    phase's wall (``enqueue_us`` -> ``enqueue_cpu_us`` / ``enqueue_us``),
    both summed over those of the leader's loaded dispatches whose
    per-phase CPU times were measured (``cpu_sampled``: one row in
    eight, a read of the thread's clock being dear): the share of the
    phase in which the thread ran; the rest it was off the CPU (the
    GIL, the disk, the device, the kernel)."""
    coll = progobs.collection() if coll is None else coll
    entry, mask = progobs._leader_loaded(coll)
    col = progobs._columns()
    wall, cpu = col.get(wall_field), col.get(wall_field[:-3] + "_cpu_us")
    if entry is None or wall is None or cpu is None:
        return None
    rows = entry["rows"][mask]
    rows = rows[rows[:, col["cpu_sampled"]] > 0]
    if len(rows) < MIN_CPU_ROWS or not rows[:, wall].sum():
        return None
    return 100.0 * float(rows[:, cpu].sum() / rows[:, wall].sum())


def ingress_cpu_per_proto_cpu(coll=None) -> float | None:
    """CPU time of the connection reader threads over CPU time of the
    protocol threads, each summed over ALL replicas of the process
    (they share one GIL): ``ingress_cpu_us`` / ``proto_cpu_us``,
    cumulative over the process."""
    coll = progobs.collection() if coll is None else coll
    ingress = proto = 0
    for entry in coll or ():
        counters = entry["metrics"]["counters"]
        if "ingress_cpu_us" not in counters or "proto_cpu_us" not in counters:
            return None
        ingress += counters["ingress_cpu_us"]
        proto += counters["proto_cpu_us"]
    return ingress / proto if proto else None


def pod_dispatch_ms(obs: dict, reduce) -> float | None:
    """``reduce`` (``np.median``, ``np.max``) over the newest pod's
    ``dispatch_ns`` ring, in ms: the host interval of every dispatch
    since its last ``begin_resident``. Read only beside a device trace
    (``obs["trace"]`` names a device): the interval is what a DEVICE
    waits for between two dispatches; on the CPU backend of a rehearsal
    the call computes the rounds itself, and says nothing."""
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    try:
        from minpaxos_tpu import obs as program
    except ImportError:
        return None
    pods = getattr(program, "process_pods", lambda: [])()
    ring = pods[-1].get("dispatch_ns") if pods else None
    if ring is None or len(ring) < MIN_POD_DISPATCHES:
        return None
    return float(reduce(np.asarray(ring))) / 1e6
