"""The yardstick's arithmetic: percentiles, trace reduction, necessary
bytes, peaks, traffic generation, the pod's stream."""

import numpy as np
import pytest

from benchmarks.lib import loadgen, podstream, stats, xplane
from benchmarks.lib.necessary_bytes import necessary_bytes_per_round
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.tables import dump_table, join_i64


def test_percentile_is_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_quartile_spread_is_the_statistics_modules():
    # statistics.quantiles([1..6], n=4) -> 1.75, 3.5, 5.25
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


def test_hist_median_bin():
    assert stats.hist_median_bin([0, 0, 10, 1]) == 3
    assert stats.hist_median_bin([5, 0, 0, 6]) == 4
    with pytest.raises(ValueError):
        stats.hist_median_bin([0, 0])


# a hand-made device line: a 10 us `while` holding two 3 us children,
# then 10 us idle, then a 5 us fusion; times in ns
EVENTS = [("while.1", 0.0, 10_000.0), ("fusion.2", 1_000.0, 3_000.0),
          ("scatter.3", 5_000.0, 3_000.0), ("fusion.2", 20_000.0, 5_000.0)]


def test_busy_union_counts_nesting_once():
    assert xplane.busy_intervals(EVENTS) == [(0.0, 10_000.0),
                                             (20_000.0, 25_000.0)]


def test_self_seconds_add_up_to_busy():
    ops = xplane.self_seconds(EVENTS)
    assert ops == pytest.approx({"while.1": 4e-6, "fusion.2": 8e-6,
                                 "scatter.3": 3e-6})
    assert sum(ops.values()) == pytest.approx(15e-6)


def test_reduce_events_idle_share_and_gap_names():
    host = [("bench.outer", 0.0, 30_000.0), ("bench.readback", 9_000.0,
                                             5_000.0)]
    out = xplane.reduce_events({"/device:TPU:0": EVENTS}, host, 30e-6)
    assert out["busy_s"] == pytest.approx(15e-6)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.5)
    assert out["device_ops"][0] == ["fusion.2", pytest.approx(8e-6)]
    gaps = dict(map(tuple, out["idle_gaps"]))
    # the 10 us gap starts inside bench.readback (the innermost span);
    # the window is taken to END at the last device event, so its other
    # 5 us lie before the first event and under no span
    assert gaps == pytest.approx({"bench.readback": 10e-6,
                                  "unattributed": 5e-6})


def test_short_name_keeps_instruction_and_shape():
    hlo = ("%copy.63 = s32[262144,2]{1,0:T(8,128)} copy(s32[262144,2]"
           "{0,1:T(2,128)S(1)} %custom-call.179)")
    assert xplane.short_name(hlo) == "%copy.63 s32[262144,2]"
    assert xplane.short_name("%while.3 = (s32[], s32[4]{0}) while(%t)") == (
        "%while.3 (tuple)")
    assert xplane.short_name("fusion.2") == "fusion.2"


def test_reduce_events_without_a_device_plane_reads_nothing():
    out = xplane.reduce_events({}, [], 1.0)
    assert out["devices"] == 0 and out["busy_s"] == 0.0


def test_busy_is_averaged_over_device_planes():
    out = xplane.reduce_events(
        {"/device:TPU:0": EVENTS, "/device:TPU:1": EVENTS[:1]}, [], 30e-6)
    assert out["busy_s"] == pytest.approx((15e-6 + 10e-6) / 2)


def test_necessary_bytes_hand_worked():
    # one group, 3 replicas, 2 proposals a round:
    # messages 2*(3-1)*2*12 = 96 lanes; log 3*2*(9+2+9) = 120 lanes;
    # table 3*2*2*5 = 60 lanes; 276 lanes * 4 B = 1104 B
    cfg = {"groups": 1, "n_replicas": 3}
    assert necessary_bytes_per_round(cfg, 2) == 1104
    assert necessary_bytes_per_round({**cfg, "groups": 64}, 2) == 64 * 1104


def test_peaks_table_has_no_default():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_dump_table_walks_the_slots():
    key_hi = np.array([0, 1, 0], np.int32)
    key_lo = np.array([7, -1, 9], np.int32)
    val = np.array([[0, -2], [3, 4], [5, 6]], np.int32)
    slot = np.array([1, 1, 0], np.int32)
    assert dump_table(key_hi, key_lo, val, slot) == {
        7: 0xFFFFFFFE, (1 << 32) | 0xFFFFFFFF: (3 << 32) | 4}
    assert join_i64(np.array([-1], np.int32),
                    np.array([-1], np.int32)).tolist() == [-1]


def test_op_codes_are_the_wires():
    from minpaxos_tpu.wire.messages import Op

    assert (loadgen.OP_PUT, loadgen.OP_GET) == (int(Op.PUT), int(Op.GET))


def test_traffic_is_a_function_of_the_seed():
    t = loadgen.Traffic(rate_hz=1000.0, key_range=100_000)
    big = 3_000_000_019  # the driver's seeds pass 2**31
    a = loadgen.arrival_offsets(t, 1000.0, 5.0, big)
    assert (a == loadgen.arrival_offsets(t, 1000.0, 5.0, big)).all()
    assert (np.diff(a) >= 0).all() and a[-1] < 5.0
    # the seed orders the work, it does not size it
    assert len(a) == 5000 == len(loadgen.arrival_offsets(t, 1000.0, 5.0, 7))
    gaps = np.diff(a)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05  # Poisson's gaps
    ops, keys, vals = loadgen.request_rows(t, 10_000, big)
    assert keys.min() >= 0 and keys.max() < 100_000
    assert abs((ops == loadgen.OP_PUT).mean() - 0.5) < 0.03
    assert len(np.unique(vals)) == len(vals) and vals.min() >= 1


def test_burst_multiplies_the_rate_inside_its_window():
    t = loadgen.Traffic(rate_hz=1000.0, key_range=10, burst_x=3.0)
    a = loadgen.arrival_offsets(t, 1000.0, 10.0, 7)
    inside = ((a >= 4.0) & (a < 6.0)).sum()
    assert abs(inside - 6000) < 6 * 6000 ** 0.5
    assert abs((a < 4.0).sum() - 4000) < 6 * 4000 ** 0.5


def test_a_stalled_generator_works_its_backlog_off_at_a_bounded_pace():
    # on time: the schedule's clock is the real one
    assert loadgen.schedule_clock(10.0, 10.0, 10.05, 1.1) == 10.05
    # the process did not run from 10 to 15: the clock resumes where it
    # stopped (not five seconds of arrivals at once) ...
    sched = loadgen.schedule_clock(10.0, 10.0, 15.0, 1.1)
    assert sched == pytest.approx(10.0 + 1.1 * loadgen.MAX_TURN_S)
    # ... and gains a tenth of a second a second: 50 s to catch up
    last, turns = 15.0, 0
    while sched < last - 1e-9:
        now = last + 0.01
        sched, last, turns = loadgen.schedule_clock(sched, last, now, 1.1), \
            now, turns + 1
    assert 48.0 < turns * 0.01 < 50.0


def test_zipf_keys_pin_mass_on_the_low_ranks():
    t = loadgen.Traffic(rate_hz=1.0, key_range=1000, zipf_s=0.99)
    _, keys, _ = loadgen.request_rows(t, 20_000, 5)
    hottest = loadgen.scramble_keys(np.arange(1), 1000)[0]
    assert (keys == hottest).mean() > 0.1 and keys.max() < 1000


@pytest.mark.parametrize("key_range", [1000, 100_000, 1 << 14])
def test_scrambling_scatters_the_hot_keys_and_loses_none(key_range):
    ranks = np.arange(key_range, dtype=np.int64)
    keys = loadgen.scramble_keys(ranks, key_range)
    assert sorted(keys.tolist()) == ranks.tolist()  # a bijection
    assert np.abs(np.diff(keys[:8])).min() > 8  # hot keys lie apart
    t = loadgen.Traffic(rate_hz=1.0, key_range=key_range, zipf_s=0.99)
    _, got, _ = loadgen.request_rows(t, 20_000, 5)
    hot = np.bincount(got, minlength=key_range).argmax()
    assert hot == keys[0] and (got == hot).mean() > 0.05


def test_podstream_is_the_programs_stream():
    """The yardstick's copy draws, row for row, what the program
    generates on the device (its own host mirror stands in here)."""
    from minpaxos_tpu.ops.workload import propose_batch_host

    g, p, ks, seed = 6, 16, 1 << 10, 3_000_000_019 % 0x7FFFFFFF
    for rnd in (2, 3, 40):
        b = propose_batch_host(5, g, p, p, 0, rnd, seed, ks)
        keys, vals = podstream.round_rows(seed, rnd, [1, 4], p, ks)
        assert (keys == b.key_lo[[1, 4], 0, :]).all()
        assert (vals == b.val_lo[[1, 4], 0, :]).all()


def test_podstream_replay_is_last_writer_wins():
    keys, vals = podstream.round_rows(9, 5, [0], 8, 4)
    want = {}
    for rnd in (5, 6):
        k, v = podstream.round_rows(9, rnd, [0], 8, 4)
        want.update(zip(k[0].tolist(), v[0].tolist()))
    assert podstream.replay(9, [5, 6], [0], 8, 4) == {0: want}
    assert set(keys[0].tolist()) <= set(range(4))


def test_a_refused_request_goes_again_soon_a_silent_one_late():
    """Over a socket pair: the worker on one end, the test as the
    server on the other."""
    import selectors
    import socket

    from minpaxos_tpu.wire.codec import FrameWriter, StreamDecoder
    from minpaxos_tpu.wire.messages import MsgKind, make_batch

    mine, servers = socket.socketpair()
    w = loadgen._Worker.__new__(loadgen._Worker)
    w.next_cmd, w.socks, w.writers = 0, [mine], [FrameWriter(mine)]
    w.leader, w.failovers, w.opened, moves = 0, 0, [mine], []
    w._connect = moves.append  # where the worker would reconnect to
    w.sel = selectors.DefaultSelector()
    w.sel.register(mine, selectors.EVENT_READ, StreamDecoder())
    ones = np.ones(3, np.int64)
    book = w._book(ones, ones, ones, seed=5)
    book["t_sent"][:] = 0.0
    w._flush(np.arange(3), book, ran=0.0)
    dec, got = StreamDecoder(), []

    def served():  # the command ids that have reached the server
        servers.settimeout(0.2)
        try:
            for _, rows in dec.feed(servers.recv(1 << 16)):
                got.extend(rows["cmd_id"].tolist())
        except TimeoutError:
            pass
        return got

    assert served() == [0, 1, 2]
    # the server refuses command 1: it goes again within the backoff
    out = FrameWriter(servers)
    out.write(MsgKind.PROPOSE_REPLY, make_batch(
        MsgKind.PROPOSE_REPLY, ok=0, cmd_id=np.array([1], np.int32), val=0,
        timestamp=0, leader=np.zeros(1, np.int8)))
    out.flush()
    w._drain_events(w.sel.select(timeout=1.0), book, ran=1.0)
    assert book["rejects"] == 1
    w._retransmit(1.0 + loadgen.REFUSED_RETRY_S, book)  # not yet: jitter
    w._retransmit(1.0 + 1.5 * loadgen.REFUSED_RETRY_S, book)
    assert served() == [0, 1, 2, 1]
    # commands 0 and 2 met silence: they wait the long timeout out
    w._retransmit(loadgen.SILENCE_RETRY_S - 0.1, book)
    assert served() == [0, 1, 2, 1]
    w._retransmit(loadgen.SILENCE_RETRY_S, book)
    assert served() == [0, 1, 2, 1, 0, 2] and book["retransmits"] == 3
    # a refusal that names another leader moves the worker there
    assert moves == []
    out.write(MsgKind.PROPOSE_REPLY, make_batch(
        MsgKind.PROPOSE_REPLY, ok=0, cmd_id=np.array([1], np.int32), val=0,
        timestamp=0, leader=np.full(1, 2, np.int8)))
    out.flush()
    w._drain_events(w.sel.select(timeout=1.0), book, ran=30.0)
    assert moves == [2] and w.failovers == 1
    w.close()
    servers.close()
