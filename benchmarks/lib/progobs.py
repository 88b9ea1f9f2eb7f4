"""What the program recorded about itself, selected for the per-layer
metrics that read it: the flight recorder's rows (one per dispatch of a
replica's tick loop, its phases in microseconds), paxtrace's sampled
per-command stage spans and the metrics registry, of every replica
server of this process, through ``minpaxos_tpu.obs.process_collection()``
— which still answers after the servers were stopped, as they are when
the readers run.

The harness hands a reader no instant of the window, so every tick
metric is a MEDIAN over the leader's loaded dispatches held in its
recorder ring (4,096 rows: a whole run). A median does not move for the
2 s of warm-up at the cell's own rate, nor for the 4 profiled seconds at
the window's end. The leader is the replica whose rows carry client
rows (``coal_occ > 0``); a loaded dispatch is such a row that reached
the device. Against a program without ``process_collection`` (or without
a field) every function returns None: the metric is left out.
"""

from __future__ import annotations

import numpy as np

#: fewer loaded dispatches, or fewer sampled chains, than this is
#: nothing to take a median of
MIN_SAMPLES = 100

PHASE_FIELDS = ("wait_us", "drain_us", "enqueue_us", "readback_us",
                "persist_us", "dispatch_us", "reply_us")


def collection() -> list[dict] | None:
    try:
        from minpaxos_tpu import obs
    except ImportError:
        return None
    collect = getattr(obs, "process_collection", None)
    return collect() if collect is not None else None


def _columns() -> dict[str, int]:
    from minpaxos_tpu.obs.recorder import FIELD_NAMES

    return {name: i for i, name in enumerate(FIELD_NAMES)}


def _idle_skip() -> int:
    from minpaxos_tpu.obs.recorder import KIND_IDLE_SKIP

    return KIND_IDLE_SKIP


def leader(coll: list[dict] | None) -> dict | None:
    """The replica most of whose rows carry client rows, newest
    registration first on a tie; None when no replica has any."""
    if not coll:
        return None
    occ = _columns()["coal_occ"]
    best, most = None, 0
    for entry in coll:
        rows = entry["rows"]
        n = 0 if rows is None or not len(rows) else int((rows[:, occ] > 0).sum())
        if n and n >= most:
            best, most = entry, n
    return best


def loaded(rows: np.ndarray) -> np.ndarray:
    """Mask of the rows that carried client rows to the device."""
    col = _columns()
    return (rows[:, col["coal_occ"]] > 0) & (rows[:, col["kind"]] != _idle_skip())


def _leader_loaded(coll):
    entry = leader(coll)
    if entry is None:
        return None, None
    mask = loaded(entry["rows"])
    if int(mask.sum()) < MIN_SAMPLES:
        return None, None
    return entry, mask


def tick_median_ms(field: str, coll=None) -> float | None:
    """Median of one phase field over the leader's loaded dispatches."""
    coll = collection() if coll is None else coll
    entry, mask = _leader_loaded(coll)
    col = _columns().get(field)
    if entry is None or col is None:
        return None
    return float(np.median(entry["rows"][mask, col])) / 1e3


def tick_cpu_share_pct(coll=None) -> float | None:
    """``cpu_us`` over (the wall since the row before - ``wait_us``),
    both summed over the leader's loaded dispatches: how much of a tick
    the protocol thread itself ran. A ratio of sums and not a median of
    ratios, because the thread's CPU clock moves in 10 ms steps on the
    chip's host (my chip runs, PR 26: every ``cpu_us`` a multiple of
    10,000), which a 16 ms tick cannot resolve row by row."""
    coll = collection() if coll is None else coll
    entry, mask = _leader_loaded(coll)
    col = _columns()
    if entry is None or "cpu_us" not in col:
        return None
    rows = entry["rows"]
    wall_us = np.diff(rows[:, col["t_ns"]], prepend=rows[0, col["t_ns"]]) / 1e3
    busy_us = wall_us - rows[:, col["wait_us"]]
    ok = mask & (busy_us > 0)
    ok[0] = False  # the first row has no row before it
    if int(ok.sum()) < MIN_SAMPLES:
        return None
    return float(rows[ok, col["cpu_us"]].sum() / busy_us[ok].sum()) * 100.0


def _chains(entry: dict) -> list[dict]:
    """The leader's sampled commands, each ``{stage: (t0, t1, aux)}``."""
    from minpaxos_tpu.obs.trace import span_chains

    spans = np.asarray(entry["spans"]["spans"], np.int64).reshape(-1, 5)
    return list(span_chains(spans).values())


def req_queue_wait_ms(coll=None) -> float | None:
    """Median ms a sampled command lay decoded in the leader's queue:
    end of its ``decode`` span to its ``drain`` stamp."""
    from minpaxos_tpu.obs.trace import ST_DECODE, ST_DRAIN

    coll = collection() if coll is None else coll
    entry = leader(coll)
    if entry is None:
        return None
    waits = [c[ST_DRAIN][0] - c[ST_DECODE][1] for c in _chains(entry)
             if ST_DECODE in c and ST_DRAIN in c]
    if len(waits) < MIN_SAMPLES:
        return None
    return float(np.median(waits)) / 1e6


def req_ticks(first: str, last: str, coll=None) -> float | None:
    """Mean number of leader dispatches whose readback fell after a
    sampled command's ``first`` stage and not after its ``last`` stage
    (stage names of ``obs.trace.STAGE_NAMES``): paxtrace says when, the
    recorder's ``t_rb_ns`` says which dispatches lay between. Commands
    drained before the ring's oldest row are left out."""
    from minpaxos_tpu.obs.trace import STAGE_NAMES

    coll = collection() if coll is None else coll
    entry = leader(coll)
    if entry is None:
        return None
    a, b = STAGE_NAMES.index(first), STAGE_NAMES.index(last)
    col = _columns()
    rows = entry["rows"]
    t_rb = np.sort(rows[rows[:, col["kind"]] != _idle_skip(), col["t_rb_ns"]])
    if not len(t_rb):
        return None
    pairs = np.array([(c[a][1], c[b][1]) for c in _chains(entry)
                      if a in c and b in c and c[a][1] >= t_rb[0]], np.int64)
    if len(pairs) < MIN_SAMPLES:
        return None
    ticks = (np.searchsorted(t_rb, pairs[:, 1], "right")
             - np.searchsorted(t_rb, pairs[:, 0], "right"))
    return float(ticks.mean())


def store_bytes_per_commit(coll=None) -> float | None:
    """The leader's ``store_flushed_bytes`` counter over its
    ``committed`` gauge: log bytes made durable per committed slot,
    cumulative over the process."""
    coll = collection() if coll is None else coll
    entry = leader(coll)
    if entry is None:
        return None
    flushed = entry["metrics"]["counters"].get("store_flushed_bytes")
    committed = entry["metrics"]["gauges"].get("committed")
    if flushed is None or not committed:
        return None
    return flushed / committed


def follower_lag_ms(coll=None) -> float | None:
    """Median, over the leader's loaded dispatches that advanced its
    frontier, of how long after that readback the SLOWER follower's
    tick loop first read back a frontier at least as high."""
    coll = collection() if coll is None else coll
    entry, mask = _leader_loaded(coll)
    if entry is None:
        return None
    col = _columns()
    kind, fr, t_rb = col["kind"], col["frontier"], col["t_rb_ns"]
    rows = entry["rows"]
    advanced = np.diff(rows[:, fr], prepend=rows[0, fr]) > 0
    lead = rows[mask & advanced]
    lags = []
    for other in coll:
        if other is entry or other["replica"] == entry["replica"] \
                or other["rows"] is None:
            continue
        f = other["rows"]
        f = f[f[:, kind] != _idle_skip()]
        if not len(f):
            continue
        # a replica's frontier never falls, so the first row at or past
        # a frontier is a binary search
        at = np.searchsorted(f[:, fr], lead[:, fr], "left")
        reached = at < len(f)
        lag = np.full(len(lead), np.nan)
        lag[reached] = f[at[reached], t_rb] - lead[reached, t_rb]
        lags.append(lag)
    if not lags:
        return None
    slower = np.max(np.stack(lags), axis=0)  # nan where one never got there
    slower = slower[~np.isnan(slower)]
    if len(slower) < MIN_SAMPLES:
        return None
    return float(np.median(slower)) / 1e6
