"""What decides ``correct``: the reference passes sound evidence and
fails each breach of a stated guarantee, the controls among them."""

import struct
import zlib

import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf
from benchmarks.lib import served_check, storefile
from benchmarks.lib.loadgen import OP_GET, OP_PUT

SLOT_DT = storefile.SLOT_DT
ACCEPTED = 3


def record(rtype: int, payload: bytes) -> bytes:
    """One v2 store record, framed as the format's description says."""
    hdr = struct.pack("<BI", rtype, len(payload))
    return hdr + struct.pack("<I", zlib.crc32(payload, zlib.crc32(hdr))) \
        + payload


def store_file(log, t_sent_of_cmd: dict) -> tuple[bytes, dict]:
    """A replica's file holding ``log``, one record and one fsync per
    row; a row is durable 0.2 ms after its request was sent (the reply
    arrives at 0.5 ms)."""
    data, t_done, size = bytearray(storefile.MAGIC), [], []
    for row in log:
        data += record(storefile.REC_SLOTS, row.tobytes())
        t_done.append(t_sent_of_cmd.get(int(row["cmd_id"]), 0.0) + 2e-4)
        size.append(len(data))
    t_done = np.maximum.accumulate(np.array(t_done))
    return bytes(data), {"t_done": t_done, "size": np.array(size, np.int64),
                         "seconds": np.full(len(size), 1e-4)}


def on_disk(ev: dict) -> dict:
    """The evidence as the runner hands it over: the logs as files."""
    ev = dict(ev)
    sent = dict(zip(ev["requests"]["cmd_id"].tolist(),
                    ev["requests"]["t_sent"].tolist()))
    stored = [store_file(log, sent) for log in ev.pop("logs")]
    ev.setdefault("files", [f for f, _ in stored])
    ev.setdefault("fsyncs", [s for _, s in stored])
    return ev


def compare(ev: dict) -> dict:
    return served_check.compare(**on_disk(ev))


def sound_evidence(n=400, seed=3):
    """A sequential system's evidence: n requests over 50 keys, logged
    in send order with a no-op fill now and then, every reply right;
    ``logs`` become files in ``on_disk``."""
    rng = np.random.default_rng(seed)
    op = np.where(rng.random(n) < 0.5, OP_PUT, OP_GET).astype(np.int64)
    key = rng.integers(0, 50, n).astype(np.int64)
    val = rng.integers(1, 1 << 62, n).astype(np.int64)
    cmd = np.arange(n, dtype=np.int64) + (1 << 27)
    t_sent = np.arange(n) * 1e-3
    rows = []
    for i in range(n):
        if i % 37 == 0:  # a no-op fill
            rows.append((len(rows), 16, ACCEPTED, 0, 0, 0, 0, -1))
        rows.append((len(rows), 16, ACCEPTED, op[i], key[i], val[i],
                     cmd[i], 4))
    log = np.array(rows, SLOT_DT)
    want, final = served_check.replay(served_check.client_rows(log))
    requests = {"cmd_id": cmd, "op": op, "key": key, "val": val,
                "t_sent": t_sent, "t_reply": t_sent + 5e-4,
                "reply_val": want.copy(), "in_window": np.arange(n) >= 40}
    return {"requests": requests, "logs": [log, log.copy(), log.copy()],
            "tables": [dict(final), dict(final), dict(final)], "quorum": 2}


def test_sound_evidence_is_correct():
    numbers = compare(sound_evidence())
    assert numbers == served_check.LIMITS == dict.fromkeys(numbers, 0)


def _wrong_get(ev):
    i = np.nonzero(ev["requests"]["op"] == OP_GET)[0][5]
    ev["requests"]["reply_val"][i] += 1


def _lost_reply(ev):
    ev["requests"]["t_reply"][47] = np.nan


def test_an_unanswered_warm_up_request_is_not_the_windows():
    ev = sound_evidence()
    ev["requests"]["t_reply"][7] = np.nan  # before the window opened
    assert compare(ev)["never_answered"] == 0


def _acked_write_not_logged(ev):
    ev["logs"] = [renumbered(log[log["cmd_id"]
                                 != ev["requests"]["cmd_id"][9]])
                  for log in ev["logs"]]
    # its effect is gone from every table too, so only the log tells
    _, final = served_check.replay(served_check.client_rows(ev["logs"][0]))
    ev["tables"] = [dict(final)] * 3


def renumbered(log):
    log = log.copy()
    log["inst"] = np.arange(len(log))
    return log


def _logged_twice(ev):
    log = ev["logs"][0]
    row = log[log["client_id"] >= 0][3:4]
    ev["logs"] = [renumbered(np.concatenate([log[:10], row, log[10:]]))] * 3


def _invented_row(ev):
    extra = np.array([(0, 16, ACCEPTED, OP_PUT, 1, 2, 99, 4)], SLOT_DT)
    ev["logs"] = [renumbered(np.concatenate([log, extra]))
                  for log in ev["logs"]]
    for t in ev["tables"]:
        t[1] = 2


def _follower_log_differs(ev):
    ev["logs"][2] = ev["logs"][2].copy()
    ev["logs"][2]["val"][20] += 1


def _table_lost_a_write(ev):
    ev["tables"][1] = dict(ev["tables"][1])
    ev["tables"][1].pop(next(iter(ev["tables"][1])))


def _logged_out_of_real_time_order(ev):
    # request 100 was answered long before request 300 was sent, yet
    # the log orders 300 first
    log = ev["logs"][0]
    c = ev["requests"]["cmd_id"]
    a = np.nonzero(log["cmd_id"] == c[100])[0][0]
    b = np.nonzero(log["cmd_id"] == c[300])[0][0]
    swapped = log.copy()
    swapped[[a, b]] = log[[b, a]]
    swapped = renumbered(swapped)
    ev["logs"] = [swapped] * 3
    want, final = served_check.replay(served_check.client_rows(swapped))
    rows = served_check.client_rows(swapped)
    order = np.argsort(rows["cmd_id"])
    ev["requests"]["reply_val"] = want[order]
    ev["tables"] = [dict(final)] * 3


def _replied_before_the_fsync(ev):
    # two of three replicas sync their logs 3 ms late: a reply that took
    # 0.5 ms left before its record was durable at a quorum
    ev.update(on_disk(ev))
    for f in ev["fsyncs"][1:]:
        f["t_done"] = f["t_done"] + 3e-3


def _tail_written_but_never_synced(ev):
    # the last replica's final fsync never happened: its file holds the
    # rows, but a power cut would take the last of them
    ev.update(on_disk(ev))
    ev["fsyncs"][2] = {k: v[:-5] for k, v in ev["fsyncs"][2].items()}


@pytest.mark.parametrize("breach, number", [
    (_wrong_get, "wrong_replies"),
    (_lost_reply, "never_answered"),
    (_acked_write_not_logged, "not_logged_once"),
    (_logged_twice, "not_logged_once"),
    (_invented_row, "invented_rows"),
    (_follower_log_differs, "log_divergence"),
    (_table_lost_a_write, "table_mismatch"),
    (_logged_out_of_real_time_order, "realtime_violations"),
    (_replied_before_the_fsync, "acked_before_durable"),
    (_tail_written_but_never_synced, "log_divergence"),
])
def test_each_breach_fails_its_number(breach, number):
    ev = sound_evidence()
    breach(ev)
    numbers = compare(ev)
    assert numbers[number] > served_check.LIMITS[number], numbers


@pytest.mark.parametrize("control, number", [
    ("served_stale_reads", "wrong_replies"),
    ("served_quorum_only", "log_divergence"),
    ("served_fsync_everysec", "acked_before_durable"),
])
def test_served_controls_come_out_not_correct(control, number):
    mod = harness.load_module(
        mf.BENCH_DIR / "controls" / f"{control}.py", "control_test")
    numbers = served_check.compare(
        **mod.apply(on_disk(sound_evidence(n=2000))))
    assert numbers[number] > served_check.LIMITS[number], numbers


def test_pod_control_comes_out_not_correct():
    from benchmarks.lib import podstream

    pod = harness.load_module(mf.BENCH_DIR / "runners" / "pod.py", "pod_test")
    control = harness.load_module(
        mf.BENCH_DIR / "controls" / "pod_lagging_replica.py", "control_pod")

    def replay(rounds):
        return podstream.replay(11, rounds, [0, 3], 64, 1 << 10)

    rounds = list(range(2, 9))
    want = replay(rounds)
    masked = {s: {k: v & 0xFFFFFFFF for k, v in want[s].items()}
              for s in want}
    tables = {s: [dict(masked[s]) for _ in range(5)] for s in want}
    ev = {"want": want, "tables": tables, "rounds": rounds, "replay": replay}
    assert sum(pod.table_mismatch(masked[s], tables[s]) for s in want) == 0
    broken = control.apply(ev)
    assert sum(pod.table_mismatch(masked[s], broken["tables"][s])
               for s in want) > 0


def slot_rows(insts, ballot=16, cmd0=100):
    rows = np.zeros(len(insts), SLOT_DT)
    rows["inst"], rows["ballot"], rows["status"] = insts, ballot, ACCEPTED
    rows["cmd_id"] = cmd0 + np.asarray(insts)
    rows["val"] = ballot
    return rows


def test_storefile_skips_a_torn_tail_and_a_corrupt_record():
    good = record(storefile.REC_SLOTS, slot_rows([0, 1, 2]).tobytes())
    bad = bytearray(record(storefile.REC_SLOTS, slot_rows([3]).tobytes()))
    bad[-1] ^= 0xFF  # a flipped payload byte: the crc fails
    later = record(storefile.REC_SLOTS, slot_rows([4]).tobytes())
    frontier = record(storefile.REC_FRONTIER, struct.pack("<i", 2))
    data = storefile.MAGIC + good + frontier + bytes(bad) + later
    out = storefile.parse(data + later[:11])
    assert out["rows"]["inst"].tolist() == [0, 1, 2]  # 3 is a hole
    assert out["corrupt_records"] == 1 and out["torn_bytes"] == 11
    assert out["frontier"] == 2 and out["rows_past_a_hole"] == 1
    assert out["first_end"][101] == len(storefile.MAGIC) + len(good)


def test_storefile_later_row_wins_unless_its_ballot_is_lower():
    data = storefile.MAGIC + b"".join(
        record(storefile.REC_SLOTS, r.tobytes()) for r in (
            slot_rows([0, 1], ballot=16), slot_rows([1], ballot=32),
            slot_rows([0], ballot=8), slot_rows([1], ballot=32, cmd0=500)))
    rows = storefile.parse(data)["rows"]
    assert rows["val"].tolist() == [16, 32]
    assert rows["cmd_id"].tolist() == [100, 501]


def test_storefile_reads_what_the_programs_store_writes(tmp_path):
    from minpaxos_tpu.runtime.stable import StableStore

    store = StableStore(str(tmp_path / "stable-store-replica0"))
    n = np.arange(5)
    store.append_slots(n, n * 0 + 16, n * 0 + ACCEPTED, n * 0 + OP_PUT,
                       n + 7, n + 9, n + 100, n * 0 + 4)
    store.append_frontier(3)
    store.flush()
    out = storefile.parse((tmp_path / "stable-store-replica0").read_bytes())
    assert out["frontier"] == 3
    assert out["rows"].tolist() == store.read_range(0, 4).tolist()
    store.close()


def test_fsync_ledger_records_what_was_durable_when(tmp_path):
    import os

    from benchmarks.lib.fsync_ledger import FsyncLedger

    ledger = FsyncLedger()
    ledger.install()
    try:
        with open(tmp_path / "log", "wb") as f:
            for chunk in (b"abc", b"defgh"):
                f.write(chunk)
                f.flush()
                os.fsync(f.fileno())
    finally:
        ledger.remove()
    got = ledger.of_file(str(tmp_path / "log"))
    assert got["size"].tolist() == [3, 8] and len(got["t_done"]) == 2
    assert (got["seconds"] >= 0).all() and os.fsync.__name__ == "fsync"
