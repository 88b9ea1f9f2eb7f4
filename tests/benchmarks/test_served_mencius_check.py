"""What decides ``correct`` in the served Mencius cell
(``benchmarks/lib/served_mencius_check.py``): hand-made evidence of a
merged three-owner log reads all zeros, one planted fault per number
moves exactly that number, the control reads not correct; and the cell
and its control end to end on the CPU at the files' toy ``rehearsal``
shape (no number there is a measurement)."""

from __future__ import annotations

import json
import os
import struct

import numpy as np
import pytest
from test_checks import record

from benchmarks import run as harness
from benchmarks.lib import served_check, served_mencius_check as smc, storefile
from benchmarks.lib.loadgen import OP_GET, OP_PUT

SLOT_DT = storefile.SLOT_DT
ACCEPTED, COMMITTED = 3, 4
N_OWNERS = 3
CELL = "mencius3_open_knee80"
CONTROL = "served_mencius_owner_order"


def test_the_copied_opcode_is_the_programs():
    from minpaxos_tpu.wire.messages import Op

    assert (smc.OP_NONE, OP_PUT, OP_GET) == (
        int(Op.NONE), int(Op.PUT), int(Op.GET))


# ------------------------------------------------- hand-made evidence

CHUNK = 30  # slots written, and requests in flight, together


def merged_file(log, under: dict) -> tuple[bytes, dict]:
    """A replica's file as three proposers leave it: rows reach it OWNER
    BY OWNER in chunks of 30 slots (never in slot order), one record and
    one fsync a row; slot 12's no-op is a takeover's, written at ballot
    17 over the owner's ballot-0 row that precedes it, and a stale
    ballot-0 copy of that row arrives once more after it; the frontier
    record comes last. ``under`` holds further rows written just before
    their slot's row in ``log`` (what a takeover found there). Chunk
    ``c``'s rows are durable at ``c`` ms + 0.2 ms (its requests were
    sent at ``c`` ms and answered 0.5 ms on)."""
    data, t_done, size = bytearray(storefile.MAGIC), [], []

    def put(row, t):
        nonlocal data
        data += record(storefile.REC_SLOTS, row.tobytes())
        t_done.append(t)
        size.append(len(data))

    stale = np.array([(12, 0, ACCEPTED, OP_PUT, 5, 99, 7, 4)], SLOT_DT)[0]
    for lo in range(0, len(log), CHUNK):
        chunk, t = log[lo:lo + CHUNK], lo // CHUNK * 1e-3 + 2e-4
        for owner in (1, 0, 2):
            for row in chunk[chunk["inst"] % N_OWNERS == owner]:
                if row["inst"] == 12:
                    put(stale, t)
                if int(row["inst"]) in under:
                    put(under[int(row["inst"])], t)
                put(row, t)
                if row["inst"] == 12:
                    put(stale, t)  # lower ballot: supersedes nothing
    data += record(storefile.REC_FRONTIER, struct.pack("<i", len(log) - 1))
    t_done.append(t_done[-1])  # fsynced with the last row
    size.append(len(data))
    return bytes(data), {"t_done": np.array(t_done),
                         "size": np.array(size, np.int64),
                         "seconds": np.full(len(size), 1e-4)}


def sound_evidence(n=300, seed=5):
    """Sound evidence with the log laid over three owners: request i
    goes to some owner and takes that owner's next slot at or past the
    log's tip; the slots skipped on the way are no-ops their owners
    ceded. The requests of one chunk of slots are in flight together
    (sent at the chunk's millisecond, answered 0.5 ms on), the next
    chunk's are sent after those replies. Every reply is the replay's."""
    rng = np.random.default_rng(seed)
    op = np.where(rng.random(n) < 0.5, OP_PUT, OP_GET).astype(np.int64)
    key = rng.integers(0, 20, n).astype(np.int64)
    val = rng.integers(1, 1 << 62, n).astype(np.int64)
    cmd = np.arange(n, dtype=np.int64) + (1 << 27)
    # runs of one or two requests on one owner: the others cede turns
    owner = np.repeat(rng.integers(0, N_OWNERS, n), rng.integers(1, 3, n))[:n]
    rows, slot = [], np.zeros(n, np.int64)
    for i in range(n):
        while len(rows) % N_OWNERS != owner[i] or len(rows) == 12:
            ballot = 17 if len(rows) == 12 else 0  # 12: a takeover's
            rows.append((len(rows), ballot, COMMITTED, 0, 0, 0, 0, -1))
        slot[i] = len(rows)
        rows.append((len(rows), 0, ACCEPTED, op[i], key[i], val[i], cmd[i],
                     4 + owner[i]))
    log = np.array(rows, SLOT_DT)
    want, final = served_check.replay(served_check.client_rows(log))
    t_sent = slot // CHUNK * 1e-3
    requests = {"cmd_id": cmd, "op": op, "key": key, "val": val,
                "t_sent": t_sent, "t_reply": t_sent + 5e-4,
                "reply_val": want.copy(), "in_window": np.arange(n) >= 30}
    noops = int(smc.is_noop(log).sum())
    return {"requests": requests, "logs": [log, log.copy(), log.copy()],
            "tables": [dict(final), dict(final), dict(final)], "quorum": 2,
            "noops_counted": [noops] * 3}


def on_disk(ev: dict) -> dict:
    ev = dict(ev)
    under = ev.pop("under", {})
    stored = [merged_file(log, under) for log in ev.pop("logs")]
    ev["files"] = [f for f, _ in stored]
    ev["fsyncs"] = [s for _, s in stored]
    return ev


def compare(ev: dict) -> dict:
    return smc.compare(**on_disk(ev))


def test_a_clean_merged_log_reads_all_zeros():
    ev = sound_evidence()
    log = ev["logs"][0]
    # the evidence is what it says: every owner has client rows and
    # ceded slots, and the file is not in slot order
    for owner in range(N_OWNERS):
        mine = log[log["inst"] % N_OWNERS == owner]
        assert smc.is_client_row(mine).any() and smc.is_noop(mine).any()
    disk = on_disk(ev)
    parsed = served_check.durable_logs(disk["files"], disk["fsyncs"])[0]
    assert (parsed["rows"] == log).all()  # by slot, takeover's row won
    assert parsed["frontier"] == len(log) - 1
    assert parsed["records"] == len(log) + 2  # the two stale copies
    numbers = smc.compare(**disk)
    assert numbers == smc.LIMITS == dict.fromkeys(numbers, 0)
    assert set(numbers) == set(served_check.LIMITS) | {"slots_unaccounted"}


def _client_slots(log, *, op=None, owner=None):
    m = smc.is_client_row(log)
    if op is not None:
        m &= log["op"] == op
    if owner is not None:
        m &= log["inst"] % N_OWNERS == owner
    return np.nonzero(m)[0]


def _row_out_of_slot_order_on_one_replica(ev):
    """Replica 1 holds two owners' rows in each other's slots."""
    log = ev["logs"][1]
    a, b = _client_slots(log, owner=0)[4], _client_slots(log, owner=1)[9]
    swapped = log[[b, a]].copy()
    swapped["inst"] = [a, b]
    log[[a, b]] = swapped


def _noop_where_a_client_row_was_acknowledged(ev):
    """An acknowledged GET's slot holds a takeover's no-op on every
    disk, over the owner's row (durable in time, and superseded by
    ballot); the program counted the slot as a no-op."""
    s = int(_client_slots(ev["logs"][0], op=OP_GET)[7])
    ev["under"] = {s: ev["logs"][0][s].copy()}
    for log in ev["logs"]:
        log[s] = (s, 17, COMMITTED, 0, 0, 0, 0, -1)
    ev["noops_counted"] = [c + 1 for c in ev["noops_counted"]]


def _stale_cross_owner_get(ev):
    """A GET sent to one owner after a PUT's reply arrived from ANOTHER
    is answered with the value before that PUT."""
    log, req = ev["logs"][0], ev["requests"]
    rows = log[smc.is_client_row(log)]
    for i in np.nonzero(rows["op"] == OP_GET)[0]:
        earlier = np.nonzero((rows["key"][:i] == rows["key"][i])
                             & (rows["op"][:i] == OP_PUT))[0]
        if len(earlier) < 2:
            continue
        put, get = rows[earlier[-1]], rows[i]
        if (put["inst"] % N_OWNERS != get["inst"] % N_OWNERS
                and put["inst"] // CHUNK < get["inst"] // CHUNK):
            r = np.nonzero(req["cmd_id"] == get["cmd_id"])[0][0]
            assert req["reply_val"][r] == put["val"]
            assert req["t_sent"][r] > req["t_reply"][
                req["cmd_id"] == put["cmd_id"]][0]
            req["reply_val"][r] = rows["val"][earlier[-2]]
            return
    raise AssertionError("no such GET in the evidence")


def _later_request_in_an_earlier_slot(ev):
    """B was first sent after A's reply had arrived from another owner,
    and lies BEFORE A in the merged order."""
    log, req = ev["logs"][0], ev["requests"]
    slots = _client_slots(log)
    for b, a in zip(slots, slots[1:]):
        if b // CHUNK < a // CHUNK and a % N_OWNERS != b % N_OWNERS:
            ra, rb = (np.nonzero(req["cmd_id"] == log["cmd_id"][s])[0][0]
                      for s in (a, b))
            req["t_sent"][rb] = req["t_reply"][ra] + 1e-4
            req["t_reply"][rb] = req["t_sent"][rb] + 5e-4
            return
    raise AssertionError("no such pair in the evidence")


def _slot_neither_row_nor_noop(ev):
    """A ceded slot holds an operation and no client, on every disk
    (and the program did not count it as a no-op)."""
    s = np.nonzero(smc.is_noop(ev["logs"][0]))[0][3]
    for log in ev["logs"]:
        log["op"][s] = OP_PUT
    ev["noops_counted"] = [c - 1 for c in ev["noops_counted"]]


def _noop_counter_off_by_one(ev):
    ev["noops_counted"][2] -= 1


FAULTS = [
    (_row_out_of_slot_order_on_one_replica, {"log_divergence": 2}),
    (_noop_where_a_client_row_was_acknowledged, {"not_logged_once": 1}),
    (_stale_cross_owner_get, {"wrong_replies": 1}),
    (_later_request_in_an_earlier_slot, {"realtime_violations": 1}),
    (_slot_neither_row_nor_noop, {"slots_unaccounted": 3}),
    (_noop_counter_off_by_one, {"slots_unaccounted": 1}),
]


@pytest.mark.parametrize("fault,moved", FAULTS,
                         ids=[f.__name__.strip("_") for f, _ in FAULTS])
def test_one_planted_fault_moves_exactly_its_number(fault, moved):
    ev = sound_evidence()
    fault(ev)
    numbers = compare(ev)
    assert {k: v for k, v in numbers.items() if v} == moved


def test_a_slot_missing_under_the_frontier_is_unaccounted():
    ev = on_disk(sound_evidence())
    # cut the last slot row out of replica 2's file, keep its frontier
    whole = ev["files"][2]
    row_len = len(record(storefile.REC_SLOTS, bytes(SLOT_DT.itemsize)))
    tail = len(record(storefile.REC_FRONTIER, bytes(4)))
    ev["files"][2] = whole[:-tail - row_len] + whole[-tail:]
    ev["fsyncs"][2] = dict(ev["fsyncs"][2],
                           size=ev["fsyncs"][2]["size"].clip(
                               max=len(ev["files"][2])))
    numbers = smc.compare(**ev)
    assert numbers["slots_unaccounted"] == 1
    assert numbers["log_divergence"] == 1  # one row shorter than replica 0's


def test_control_answers_in_owner_order_and_reads_not_correct():
    control = harness.load_module(
        harness.ROOT / "benchmarks" / "controls" / f"{CONTROL}.py",
        "mencius_control")
    ev = on_disk(sound_evidence())
    got = control.apply(ev)
    assert got["files"] is ev["files"] and got["tables"] is ev["tables"]
    numbers = smc.compare(**got)
    assert numbers["wrong_replies"] > 20
    assert {k for k, v in numbers.items() if v} == {"wrong_replies"}
    # a log with ONE loaded owner is the same in both orders
    solo = sound_evidence()
    keep = served_check.client_rows(solo["logs"][0])
    keep = keep[keep["inst"] % N_OWNERS == 0]["cmd_id"]
    for log in solo["logs"]:
        drop = smc.is_client_row(log) & ~np.isin(log["cmd_id"], keep)
        log[drop] = [(s, 0, COMMITTED, 0, 0, 0, 0, -1)
                     for s in np.nonzero(drop)[0]]
    want, final = served_check.replay(
        served_check.client_rows(solo["logs"][0]))
    req = solo["requests"]
    kept = np.isin(req["cmd_id"], keep)
    solo["requests"] = {k: v[kept] for k, v in req.items()}
    solo["requests"]["reply_val"] = want.copy()
    solo["tables"] = [dict(final)] * 3
    solo["noops_counted"] = [int(smc.is_noop(solo["logs"][0]).sum())] * 3
    solo = on_disk(solo)
    assert smc.compare(**solo) == smc.LIMITS
    assert smc.compare(**control.apply(solo)) == smc.LIMITS


# ----------------------------------------- the runner, end to end (CPU)

def _run(capsys, *args) -> tuple[dict, str]:
    assert harness.main(["--workload", CELL, "--seed", "3000035777",
                         "--seconds", "3", "--rehearse-cpu", *args]) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_traced_rehearsal_is_correct_over_three_owners(capsys):
    line, err = _run(capsys, "--trace", "1")
    assert line["correct"] is True, line["checks"]
    assert {k: c["limit"] for k, c in line["checks"].items()} == smc.LIMITS
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["attempted"] == 900 and line["failed"] == 0
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert value["compiles_in_window.served"] == 0.0
    # 8 sessions over 3 owners: 3 / 3 / 2, so three eighths at the most
    assert 33.0 < value["owner_proposal_share_max.served"] < 45.0
    assert 0.0 <= value["noop_slot_pct.served"] < 60.0
    assert value["owner_dispatches_per_s.served"] > 0
    assert "3 replicas serving, protocol mencius" in err
    assert "quiesced: True" in err


def test_control_rehearsal_reads_not_correct(capsys):
    line, _ = _run(capsys, "--trace", "0", "--control", CONTROL)
    assert line["correct"] is False and line["control"] == CONTROL
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad == {"wrong_replies"}
    assert set(line["metrics"]) == {"reply_p50_ms", "reply_p95_ms",
                                    "setup_s"}


def test_a_program_without_the_stage_is_refused_at_once(monkeypatch):
    """Against the parent's program the cell ends before anything is
    built: a clean non-zero exit, not a hang."""
    from minpaxos_tpu.obs import trace

    monkeypatch.delattr(trace, "ST_OWN_COMMIT")
    with pytest.raises(SystemExit) as exit_:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                      "--rehearse-cpu"])
    assert exit_.value.code not in (0, None)
    assert "own_commit" in str(exit_.value.code)
    assert not (harness.ROOT / ".bench_scratch" / f"{CELL}-{os.getpid()}"
                ).exists()
