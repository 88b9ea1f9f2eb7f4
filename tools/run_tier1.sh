#!/usr/bin/env bash
# The one blessed test entry point.
#
#   tools/run_tier1.sh          — the ROADMAP.md tier-1 line, verbatim
#                                 (full 'not slow' suite + DOTS_PASSED
#                                 count; ~10-13 min on the 1-core host)
#   tools/run_tier1.sh smoke    — fast pre-commit smoke: runtime + wire
#                                 units only (~2 min)
#
# Builders and CI invoke this instead of re-deriving the pytest flags:
# the tier-1 command's exact flags (marker filter, plugin disables,
# collection-error tolerance) ARE the acceptance contract, and ad-hoc
# variations have produced incomparable pass counts before.
set -u
cd "$(dirname "$0")/.."

# paxlint first: pure-AST consensus-aware lint (ANALYSIS.md), no JAX
# import, runs cold in ~2 s. A hot-path host sync, a wire-contract
# drift, or a lock-discipline break fails the build before any test
# boots a cluster.
echo "== paxlint =="
python tools/lint.py || exit 1

# paxmon smoke second: still no JAX import (~2 s). Gates the
# recorder-overhead contract (obs is default-ON in the runtime, so a
# hot-path regression there is a throughput regression everywhere)
# and the paxtop --once --json / TRACE-schema end-to-end path against
# a real master + control-plane stub (OBSERVABILITY.md).
echo "== paxmon smoke (recorder overhead + paxtop --once --json) =="
python tools/obs_smoke.py || exit 1

# paxmc smoke third: bounded model checking of the real protocol
# kernels — all 3 protocols explored exhaustively at the smoke bounds
# (every per-link delivery order, one drop, one dup, a concurrent
# election), every reached state held to the shared invariant suite,
# plus a seeded broken-quorum mutant that MUST yield a replayable
# counterexample (VERIFY.md). First JAX boot of the gate; budget
# clock starts after the first protocol's jit compile.
echo "== paxmc smoke (bounded model check: 3 protocols + quorum mutant) =="
env JAX_PLATFORMS=cpu python tools/mc.py --smoke || exit 1

# paxray smoke fourth: the device-resident loop and its telemetry
# contract (ISSUE 9) — telemetry-on vs telemetry-off dispatch wall
# within 2% (min-of-N, order-alternating A/B), byte-identical protocol
# state after the drain, and a validated merged host+device Chrome
# trace with the device rounds under the reserved pid. ~45 s including
# the two dispatch-variant compiles. (That the drain is exact and the
# on-device latency histogram complete is held by the resident tests
# of tests/test_workload.py, in the pytest run below.)
echo "== paxray smoke (telemetry overhead <=2% + merged device trace) =="
env JAX_PLATFORMS=cpu python tools/obs_smoke.py --resident || exit 1

# paxchaos smoke fifth: two fixed-seed fault schedules (partition-heal
# + 10% loss/reorder) against a real in-process cluster, checked with
# the SAME invariant predicates the model checker just proved at small
# bounds (ROBUSTNESS.md). Budget clock starts after the first run so
# the one-time jit compile doesn't count.
echo "== paxchaos smoke (2 seeded fault schedules + invariant checker) =="
env JAX_PLATFORMS=cpu python tools/chaos.py --smoke || exit 1

# paxsoak smoke sixth: the scenario driver end-to-end (ISSUE 18) —
# a 2-phase manifest (warmup + a micro overload burst) through the
# open-loop sharded swarm against a real cluster, checking EV_PHASE
# landed on every replica's journal, exactly-once held across shards
# (0 lost), and the joined scorecard is well-formed. Same compiled
# cluster shape as the chaos smoke above (JAX + the dispatch variants
# are warm); phase walls are manifest-fixed, ~40 s total.
echo "== paxsoak smoke (2-phase open-loop scenario + joined scorecard) =="
env JAX_PLATFORMS=cpu python tools/soak.py --smoke || exit 1

# The concurrent-client swarm leg (ISSUE 15) rides the pytest suite
# below: tests/test_swarm.py drives 64 real closed-loop TCP sessions
# through the ingress coalescer against an in-process cluster (~18 s,
# no new compiled variants); the 1024-session overload leg is marked
# `slow` and runs only in the full suite (pytest tests/ -m slow).

if [ "${1:-}" = "smoke" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
        -k "runtime_units or wire or fused" \
        -p no:cacheprovider -p no:xdist -p no:randomly
fi

# ROADMAP.md tier-1 verify line, verbatim:
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
