"""paxray: on-device telemetry row construction for the resident loop.

PR 8 made the measured loop fully device-resident and thereby
invisible: two scalars per dispatch are its whole host-visible
surface, so nothing inside a k-round dispatch — where ROADMAP item 1
says the remaining cost lives — could be observed without breaking the
residency contract. This module is the device half of the fix: a pure
jnp constructor for ONE int32 telemetry row per protocol round,
traced inside ``sharded_run_resident``'s scan body and accumulated
into a donated ``[rounds, N_TEL_FIELDS]`` ring that the host reads
back exactly once after the measured window (the same post-window
discipline as the latency histogram — paxlint's ``resident-loop``
rule still holds over the dispatch path with telemetry enabled).

The field layout is canonical in ``obs/recorder.py`` (numpy-only, so
paxtop and the smoke gates import it without JAX) and imported here;
``obs.recorder.device_round_events`` renders the readback as Perfetto
device-round tracks under the reserved pid. Telemetry writes touch
ONLY the telemetry buffer — protocol state is byte-identical with
telemetry on or off (pinned by tests/test_paxray.py), and a zero-row
buffer drops the writes from the trace entirely (it compiles the
dispatch without them).

Per-phase latency decomposition is what makes consensus systems
tunable in production ("Paxos in the Cloud", PAPERS.md 1404.6719);
the per-round rows here plus the ``px.*`` scopes of a device trace
(ops/sections.py) are that decomposition for the resident loop.
"""

from __future__ import annotations

import jax.numpy as jnp

from minpaxos_tpu.obs.recorder import (
    N_TEL_FIELDS,
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
)

__all__ = ["telemetry_row", "N_TEL_FIELDS", "TEL_FIELD_NAMES"]


def telemetry_row(round_idx, committed_delta, in_flight, assigned,
                  injected_rows, inbox_rows, claim_rows, prepared_shards,
                  inbox_hwm):
    """One ``[N_TEL_FIELDS]`` int32 telemetry row, field order pinned
    to the obs/recorder.py layout (asserted below at import time, and
    against TEL_FIELD_NAMES by tests/test_paxray.py).

    All arguments are traced scalars; callers compute them from the
    scan carry before/after the round step (parallel/sharded.py), so
    this stays ~10 scalar ops per round — noise next to the step
    kernels, which is what lets the obs_smoke gate hold telemetry-on
    dispatch wall within 2% of telemetry-off."""
    fields = {
        TEL_ROUND: round_idx,
        TEL_COMMITTED: committed_delta,
        TEL_IN_FLIGHT: in_flight,
        TEL_ASSIGNED: assigned,
        TEL_INJECTED: injected_rows,
        TEL_INBOX_ROWS: inbox_rows,
        TEL_CLAIM_ROWS: claim_rows,
        TEL_PREPARED: prepared_shards,
        TEL_INBOX_HWM: inbox_hwm,
    }
    assert sorted(fields) == list(range(N_TEL_FIELDS))
    return jnp.stack([jnp.asarray(fields[i], jnp.int32)
                      for i in range(N_TEL_FIELDS)])
