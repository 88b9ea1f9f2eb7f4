"""paxray: device-side telemetry for the resident loop (ISSUE 9).

Contract pinned here:

* telemetry is a PURE OBSERVER — protocol state, committed results and
  the latency histogram are byte-identical with the ring armed or not
  (``telemetry_rounds`` 0 against N), and the readback is
  deterministic across reruns from the same seed;
* the ring rides the donation discipline (consumed per dispatch like
  the state tree) and its row layout is pinned against the canonical
  obs/recorder.py field table;
* the unified timeline renders: device-round events merge with host
  flight-recorder events into a schema-v4 Chrome trace that validates,
  with the device tracks under the reserved pid — and a host event
  squatting on the reserved pid FAILS validation.

Shapes deliberately mirror tests/test_workload.py (same cfg/g/
ext_rows/k) so the telemetry-off dispatch shares its compiled
dispatch, and every telemetry-on test shares ONE (64-row ring)
compilation — tier-1 budget discipline.
"""

from __future__ import annotations

import jax
import numpy as np

from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.obs.recorder import (
    DEVICE_PID,
    SCHEMA_VERSION,
    TEL_ASSIGNED,
    TEL_CLAIM_ROWS,
    TEL_COMMITTED,
    TEL_FIELD_NAMES,
    TEL_IN_FLIGHT,
    TEL_INBOX_HWM,
    TEL_INBOX_ROWS,
    TEL_INJECTED,
    TEL_PREPARED,
    TEL_ROUND,
    FlightRecorder,
    chrome_trace,
    device_round_events,
    telemetry_valid_rows,
    validate_chrome_trace,
)
from minpaxos_tpu.ops.telemetry import N_TEL_FIELDS, telemetry_row
from minpaxos_tpu.parallel.sharded import DONATION, ShardedCluster

SMALL = MinPaxosConfig(
    n_replicas=3, window=256, inbox=256, exec_batch=64, kv_pow2=10,
    catchup_rows=16, recovery_rows=16)

TEL_ROUNDS = 64  # ONE ring shape for every telemetry-on test


def _boot(seed=5, tel_rounds=0) -> ShardedCluster:
    sc = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8, seed=seed)
    sc.elect(0)
    sc.begin_resident(telemetry_rounds=tel_rounds)
    return sc


def _run(sc: ShardedCluster, dispatches=3, k=6, p=24):
    for _ in range(dispatches):
        committed, in_flight = sc.run_resident(k, p)
    for _ in range(6):
        committed, in_flight = sc.run_resident(k, 0)
        if in_flight == 0:
            break
    return committed, in_flight


# ------------------------------------------------------------- layout


def test_telemetry_row_layout_pinned_to_recorder():
    """ops/telemetry.py's traced constructor and obs/recorder.py's
    canonical field table cannot drift: a row built from distinct
    per-field values must land each value at its named index."""
    vals = dict(round_idx=10, committed_delta=11, in_flight=12,
                assigned=13, injected_rows=14, inbox_rows=15,
                claim_rows=16, prepared_shards=17, inbox_hwm=18)
    row = np.asarray(telemetry_row(**vals))
    assert row.shape == (N_TEL_FIELDS,) and row.dtype == np.int32
    assert len(TEL_FIELD_NAMES) == N_TEL_FIELDS
    assert row[TEL_ROUND] == 10 and row[TEL_COMMITTED] == 11
    assert row[TEL_IN_FLIGHT] == 12 and row[TEL_ASSIGNED] == 13
    assert row[TEL_INJECTED] == 14 and row[TEL_INBOX_ROWS] == 15
    assert row[TEL_CLAIM_ROWS] == 16 and row[TEL_PREPARED] == 17
    assert row[TEL_INBOX_HWM] == 18


# ------------------------------------------------------ parity / purity


def test_telemetry_parity_state_byte_identical():
    """THE acceptance pin: telemetry on vs off —
    same committed totals, same exact latency histogram, and a
    byte-identical final cluster state from the same seed."""
    sc_off = _boot(tel_rounds=0)
    c_off, f_off = _run(sc_off)
    hist_off = sc_off.end_resident()

    sc_on = _boot(tel_rounds=TEL_ROUNDS)
    c_on, f_on = _run(sc_on)
    tel = sc_on.resident_telemetry()
    hist_on = sc_on.end_resident()

    assert (c_off, f_off) == (c_on, f_on)
    assert f_on == 0  # drained exactly — accounting below is total
    assert np.array_equal(hist_off, hist_on)
    for a, b in zip(jax.tree_util.tree_leaves(sc_off.ss),
                    jax.tree_util.tree_leaves(sc_on.ss)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the ring actually observed the run it rode along with
    assert len(tel) > 0
    assert int(tel[:, TEL_COMMITTED].sum()) == c_on
    assert int(tel[:, TEL_ASSIGNED].sum()) == c_on
    assert int(tel[-1, TEL_IN_FLIGHT]) == 0


def test_telemetry_determinism_pin():
    """Same seed => identical telemetry rows across fresh runs (the
    readback is part of the reproducible record); a different seed
    changes the stream but not the accounting identities."""
    runs = []
    for seed in (3, 3, 4):
        sc = _boot(seed=seed, tel_rounds=TEL_ROUNDS)
        committed, in_flight = _run(sc)
        runs.append((committed, sc.resident_telemetry()))
        sc.end_resident()
    assert np.array_equal(runs[0][1], runs[1][1])
    assert runs[0][0] == runs[2][0]  # same protocol progress...
    assert int(runs[2][1][:, TEL_COMMITTED].sum()) == runs[2][0]


def test_telemetry_content_semantics():
    """Field-level sanity at a hand-checkable scale: rounds are
    consecutive absolute indices, the steady flag is saturated after
    the election, injected rows follow the proposal schedule, inbox
    rows appear once routed traffic exists, and claim rows never
    exceed commits."""
    sc = _boot(tel_rounds=TEL_ROUNDS)
    committed, _ = _run(sc, dispatches=2)
    tel = sc.resident_telemetry()
    sc.end_resident()
    g, p = 2, 24
    rounds = tel[:, TEL_ROUND]
    assert (np.diff(rounds) == 1).all()  # one row per round, no holes
    assert (tel[:, TEL_PREPARED] == g).all()  # steady post-election
    # 2 proposing dispatches of 6 rounds, then drain rounds inject 0
    assert (tel[:12, TEL_INJECTED] == g * p).all()
    assert (tel[12:, TEL_INJECTED] == 0).all()
    assert tel[0, TEL_INBOX_ROWS] == 0  # nothing routed before round 1
    assert (tel[1:12, TEL_INBOX_ROWS] > 0).all()
    # the occupancy column feeding adaptive capacity (PR 11): the max
    # DELIVERED per-inbox load (routed + injected) is bounded by the
    # cross-cluster totals and by the static capacity, positive
    # exactly when anything was delivered — and round 0 (nothing
    # routed yet, p rows injected at the leader) pins the injected
    # contribution exactly
    assert ((tel[:, TEL_INBOX_HWM]
             <= tel[:, TEL_INBOX_ROWS] + tel[:, TEL_INJECTED]).all()
            and (tel[:, TEL_INBOX_HWM] <= SMALL.inbox + 32).all())
    assert ((tel[:, TEL_INBOX_HWM] > 0)
            == ((tel[:, TEL_INBOX_ROWS] + tel[:, TEL_INJECTED]) > 0)).all()
    assert tel[0, TEL_INBOX_HWM] == p
    assert int(tel[:, TEL_CLAIM_ROWS].sum()) <= committed
    assert int(tel[:, TEL_COMMITTED].sum()) == committed


def test_telemetry_ring_wraps_to_last_rounds():
    """More rounds than ring rows: the ring keeps the LAST
    ``TEL_ROUNDS`` rounds (a ring, not a truncation), still
    consecutive."""
    sc = _boot(tel_rounds=TEL_ROUNDS)
    # 13 dispatches x 6 rounds = 78 rounds > 64 ring rows
    for _ in range(10):
        sc.run_resident(6, 24)
    for _ in range(3):
        committed, in_flight = sc.run_resident(6, 0)
    tel = sc.resident_telemetry()
    last_round = sc._seed - 1  # rounds are 0-indexed by the _seed ctr
    sc.end_resident()
    assert len(tel) == TEL_ROUNDS
    assert int(tel[-1, TEL_ROUND]) == last_round
    assert (np.diff(tel[:, TEL_ROUND]) == 1).all()


def test_telemetry_buffer_is_donated():
    """The ring rides the donation discipline: consumed per dispatch
    like the state tree and the other bookkeeping buffers."""
    assert DONATION["sharded_run_resident"] is True
    sc = _boot(tel_rounds=TEL_ROUNDS)
    old_tel = sc._telemetry
    old_ballot = sc.ss.states.ballot
    sc.run_resident(6, 8)
    assert old_tel.is_deleted()
    assert old_ballot.is_deleted()


# ------------------------------------------------------ unified timeline


def _synthetic_dispatches(rows, t0_ns=1_000_000_000, wall_ns=2_000_000,
                          k=6):
    """A dispatch log covering the telemetry rows, k rounds per
    dispatch, on the monotonic_ns clock the host recorder uses."""
    rows = telemetry_valid_rows(rows)
    first, last = int(rows[0, TEL_ROUND]), int(rows[-1, TEL_ROUND])
    disp, t = [], t0_ns
    r = first
    while r <= last:
        disp.append({"t0_ns": t, "t1_ns": t + wall_ns, "round0": r,
                     "k": k})
        t += wall_ns
        r += k
    return disp


def test_merged_device_host_trace_validates_v4():
    """The tentpole's piece 3: real telemetry readback + host
    flight-recorder rows merge into ONE schema-v4 Chrome trace that
    validates, device rounds under the reserved pid, host ticks under
    replica pids, with the frontier/in-flight counter tracks
    present."""
    sc = _boot(tel_rounds=TEL_ROUNDS)
    committed, _ = _run(sc, dispatches=2)
    tel = sc.resident_telemetry()
    sc.end_resident()

    rec = FlightRecorder(64)
    t = 1_000_000_000
    for i in range(4):
        t += 2_000_000
        rec.record(t, 1, 6, 48, 0, 100 + i, 0, 5, 30, 500, 0, 20, 30,
                   10, t - 100_000)
    disp = _synthetic_dispatches(tel)
    events = rec.to_events(pid=0) + device_round_events(tel, disp,
                                                        n_shards=2)
    trace = chrome_trace(events)
    assert trace["otherData"]["paxmonSchemaVersion"] == SCHEMA_VERSION == 9
    assert validate_chrome_trace(trace) == []

    dev = [e for e in events if e.get("cat") == "device_round"]
    assert len(dev) == len(tel)
    assert all(e["pid"] == DEVICE_PID for e in dev)
    assert all(e["name"] == "round:steady" for e in dev)  # post-elect
    args0 = dev[0]["args"]
    assert set(args0) == set(TEL_FIELD_NAMES)
    cnames = {e["name"] for e in events if e["ph"] == "C"
              and e["pid"] == DEVICE_PID}
    assert {"device_frontier", "device_in_flight"} <= cnames
    # the device_frontier counter integrates to the committed total
    fr = [e["args"]["device_frontier"] for e in events
          if e.get("name") == "device_frontier"]
    assert fr[-1] == committed
    # host events stayed on their own pid
    assert all(e["pid"] == 0 for e in events
               if e.get("cat") in ("tick", "phase"))


def test_reserved_pid_is_enforced():
    """A host-looking event on the reserved device pid, or a device
    event off it, must fail validation — the merge contract."""
    good = {"name": "tick:full", "cat": "tick", "ph": "X", "ts": 1.0,
            "dur": 1.0, "pid": 0, "tid": 0}
    squatter = dict(good, pid=DEVICE_PID)
    errs = validate_chrome_trace(chrome_trace([good, squatter]))
    assert errs and "reserved" in errs[0]
    stray = {"name": "round:steady", "cat": "device_round", "ph": "X",
             "ts": 1.0, "dur": 1.0, "pid": 3, "tid": 0}
    errs = validate_chrome_trace(chrome_trace([stray]))
    assert errs and "reserved pid" in errs[0]


def test_device_round_events_skips_uncovered_rounds():
    """Rounds with no covering dispatch (telemetry of a window the
    host never logged) are skipped, not misplaced at t=0."""
    row = np.asarray(telemetry_row(5, 1, 2, 3, 4, 5, 6, 2, 3))[None]
    evs = device_round_events(row, [{"t0_ns": 0, "t1_ns": 1000,
                                     "round0": 99, "k": 2}], n_shards=2)
    assert evs == []
