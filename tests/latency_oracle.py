"""The host-side reference of the device latency histogram.

``tests/test_workload.py`` holds the resident scan's on-device
histogram to this reconstruction from per-round cursor histories; a bug
here would let a wrong histogram pass, so it has hand-computed tests of
its own (``tests/test_bench_units.py``).
"""

from __future__ import annotations

import numpy as np


def latency_rounds(uptos, crts, round_ms):
    """Per-slot quorum-decision latency from cursor histories.

    uptos/crts: [T, G] leader cursors AFTER each round (round r is row
    r). Slot s of shard sh is injected during the round t_in where crt
    first exceeds s, and committed during the round t_c where upto
    first reaches s. Latency = (t_c - t_in + 1) rounds (inject + commit
    in the same round = 1 round), converted to ms at ``round_ms``.
    Only slots committed by the end are counted — the caller drains the
    log so that is ALL injected slots (no tail censoring). Returns
    (p50_ms, p99_ms, n_samples, uncommitted)."""
    T, G = uptos.shape
    lats = []
    # slots assigned but never committed by the end of the run (drain
    # cap hit): these are the SLOWEST slots and are necessarily absent
    # from the sample, so report their count instead of pretending the
    # tail is complete
    uncommitted = int(np.maximum(crts[-1] - 1 - uptos[-1], 0).sum())
    for sh in range(G):
        first = int(crts[0, sh])  # assigned before measurement began
        last = int(uptos[-1, sh])
        slots = np.arange(first, last + 1)
        if len(slots) == 0:
            continue
        t_in = np.searchsorted(crts[:, sh], slots, side="right")
        t_c = np.searchsorted(uptos[:, sh], slots, side="left")
        ok = (t_in < T) & (t_c < T)
        lats.append((t_c[ok] - t_in[ok] + 1).astype(np.float64))
    if not lats:
        return float("nan"), float("nan"), 0, uncommitted
    lat = np.concatenate(lats) * round_ms
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)),
            int(lat.size), uncommitted)
