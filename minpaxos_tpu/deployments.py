"""The deployment shapes this program states: BASELINE.json configs 1-5.

One home for the sizes that ``chip_smoke.py`` runs on the chip and that
the benchmark's configuration files (``benchmarks/configs/*.json``) are
held to by ``tests/benchmarks``. A shape is a ``MinPaxosConfig`` plus
how many groups share the device and how many proposals a round
carries; the sizing rules below are what the chip taught (PRs 21 and
28) and are pinned by ``tests/test_deployments.py``.

No JAX is imported until a config is built.
"""

from __future__ import annotations

#: (g, w, p, k) — g shards x w-slot windows = concurrent instances
#: resident on the device, p proposals per shard per round, k rounds
#: per dispatch. The on-chip shape is BASELINE config 5's 1,048,576
#: concurrent instances; the CPU shape is a harness check.
TPU_SHAPE = (256, 4096, 512, 32)
CPU_SHAPE = (8, 512, 64, 8)

#: BASELINE config 1, the served N=3 ``-min -durable`` cluster: window
#: 2048 / inbox 1024 / table 2^18 (the 100k-key workload at 0.38 load,
#: comfortable for the two-choice table) / exec batch 128. Upstream
#: fixes none of these; the step is window-linear with a table-sized
#: floor, so they are kept as small as the load allows.
SERVER_SHAPE = ["-window", "2048", "-inbox", "1024", "-kvpow2", "18",
                "-execbatch", "128"]

#: the served N=3 ``-m -durable`` cluster (upstream server flag ``-m``,
#: clients round-robin over all owners, ``-e``): window 4096 / inbox
#: 2048 / table 2^18 / exec batch 512. Every replica proposes, so an
#: inbox holds its own clients' rows AND two peers' ACCEPT, COMMIT and
#: SKIP rows, and the log takes a slot for every turn an owner cedes:
#: ``SERVER_SHAPE``'s 2048 / 1024 starved it on the CPU. The chip's
#: readings behind each size are in ``benchmarks/configs/
#: mencius3_durable.json`` under ``assumed``.
MENCIUS_SERVER_SHAPE = ["-window", "4096", "-inbox", "2048", "-kvpow2", "18",
                        "-execbatch", "512"]


def cpu_catchup_rows(p: int) -> int:
    """Catch-up rows of the CPU harness shape: 2p, held to [64, 512].
    The rule is the chip's (``headline_config``): catch-up must OUTPACE
    the live commit stream while a revived victim's frontier is pinned
    at its hole — cu >= 2p reheals, cu <= p/2 never does."""
    return max(64, min(512, 2 * p))


def cpu_key_space(p: int) -> int:
    """Workload key space of the CPU harness shape: the smallest power
    of two >= max(256, p). The stride-walk key schedule
    (ops/workload.py) is duplicate-free within a round only while
    rows <= key_space."""
    return 1 << max(8, (p - 1).bit_length())


def cpu_kv_pow2(p: int) -> int:
    """KV capacity to go with ``cpu_key_space``: 4x the key space, the
    chip's rule (``headline_config``)."""
    return max(10, (cpu_key_space(p) - 1).bit_length() + 2)


def headline_config(on_tpu: bool, w: int, p: int):
    """(cfg, key_space) of the MinPaxos N=5 pod run (BASELINE config 5)
    at window ``w`` and ``p`` proposals per shard per round.

    KV capacity is 4x the workload key space (2^16 entries for 16k keys
    on the chip). The greedy two-choice table has no relocation, so 2x
    headroom was not enough: at 2^15 the first checked runs at g=256
    (PR 21, chip and CPU) lost inserts — acknowledged writes missing
    from the table.

    Inbox: acks are run-length compressed in the kernel, so a
    follower's inbox holds p ACCEPT rows plus the appendices — two
    catch-up chunks (catch-up and retry), recovery rows and 64 gossip
    rows — and the leader's holds ~R compressed ack rows.

    Catch-up: while a revived victim still has a hole, its commit
    FRONTIER is pinned at the hole, so catch-up must outpace the live
    commit stream, not just clear the gap: the leader serves one peer
    per round, so the hole closes at ~cu/2 per round while the retained
    window (w//2 slots) slides away from it at p per round. cu >= 2p
    reheals in about one dispatch; at 1p the victim reached the
    leader's frontier-at-revive and then froze behind the window for
    good (first checked run on the chip, PR 21). Hence max(512, 2p)."""
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig

    cu_rows = max(512, 2 * p) if on_tpu else cpu_catchup_rows(p)
    cfg = MinPaxosConfig(
        n_replicas=5, window=w, inbox=p + 2 * cu_rows + 64 + 64,
        exec_batch=p, kv_pow2=16 if on_tpu else cpu_kv_pow2(p),
        catchup_rows=cu_rows, recovery_rows=64)
    return cfg, (1 << 14) if on_tpu else cpu_key_space(p)


def side_shapes(on_tpu: bool) -> dict:
    """BASELINE configs 2-4 as ``name -> (cfg, shards, proposals per
    round [per owner under mencius], rounds per dispatch, protocol)``.
    ``on_tpu`` changes only the rounds per dispatch. Shards x window is
    the source's instance count (1k, 64k, 64k); ``exec_batch`` holds
    one round's commits; the inbox holds the round's proposals plus the
    appendices (classic: two catch-up chunks, recovery rows, 64 gossip
    rows, as in ``headline_config``; mencius: a catch-up chunk from
    each of the four peers and recovery rows — its fullest healthy
    inbox held 1,028 rows, PR 28)."""
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig
    from minpaxos_tpu.models.paxos import classic_config

    return {
        # config 2: classic paxos, 1 client, sequential instances
        # (1 proposal per round — pipelined-sequential)
        "paxos_sequential": (
            classic_config(n_replicas=5, window=1024, inbox=256,
                           exec_batch=32, kv_pow2=12,
                           catchup_rows=32, recovery_rows=32),
            1, 1, 128 if on_tpu else 32, "classic"),
        # config 3: classic paxos, 16 clients (= 16 shards), 64k
        # concurrent instances
        "paxos_64k": (
            classic_config(n_replicas=5, window=4096,
                           inbox=256 + 2 * 64 + 128, exec_batch=256,
                           kv_pow2=14, catchup_rows=64,
                           recovery_rows=64),
            16, 256, 32 if on_tpu else 8, "classic"),
        # config 4: mencius, 5 rotating owners, 64k instances.
        # catchup_rows = the per-step COMMIT-broadcast chunk in the
        # mencius kernel; it must exceed the per-owner proposal rate
        # (64/round) or the frontier can never drain its backlog
        "mencius_64k": (
            MinPaxosConfig(n_replicas=5, window=4096,
                           inbox=2048, exec_batch=320,
                           kv_pow2=14, catchup_rows=128,
                           recovery_rows=64, noop_delay=8),
            16, 64, 32 if on_tpu else 8, "mencius"),
    }
