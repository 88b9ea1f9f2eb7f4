"""minpaxos_tpu — a TPU-native state-machine-replication framework.

A brand-new framework with the capabilities of arobertlin/MinPaxos (a
Go Multi-Paxos replicated key-value store; see SURVEY.md at the repo
root), re-designed for TPU hardware: quorum voting over thousands of
independent Paxos instances is computed as batched, data-parallel array
ops inside single XLA-compiled steps (JAX: jit / vmap / lax.scan, laid
over a device mesh with NamedSharding), instead of one goroutine per
message.

Subpackages
-----------
utils      Low-level utilities (dlog, bitvec, bloomfilter, clock) —
           array-native counterparts of reference src/dlog, src/bitvec,
           src/bloomfilter, src/rdtsc.
wire       Message schemas + columnar binary codec — counterpart of
           reference src/fastrpc + src/*proto packages.
ops        Device kernels: batched quorum math, vectorized KV state
           machine, parallel execution engine.
models     Consensus protocols over the quorum kernels: bareminpaxos
           (MinPaxos), classic paxos, mencius — counterpart of reference
           src/bareminpaxos, src/paxos, src/mencius.
parallel   Mesh / sharding layer: shard x replica device meshes, pjit
           partitioning of the cluster step, ICI collectives.
runtime    Host-side runtime: TCP peer mesh + client listener +
           batch-draining event loop (replica.py, transport.py —
           counterpart of src/genericsmr), master coordination
           (master.py — src/master), durable redo log + crash
           recovery (stable.py — the reference's stable-store files),
           and the benchmark client engine (client.py — closed-loop,
           retry/failover, latency; counterpart of src/client*,
           src/clientretry, src/clientlat).
native     Optional C++ fast paths (cycle clock, wire-frame stream
           scan) — counterpart of src/rdtsc, the reference's only
           native component. Build: python -m minpaxos_tpu.native.build.
cli        server / master / client entry points (flag-compatible with
           reference src/server, src/master, src/client; the client
           covers -lat / -tot / open-loop modes).

Fault injection is programmatic rather than a subpackage: pod-mode
``Cluster.kill/revive`` masks and the TCP harness in
tests/test_distributed.py replace the reference's kill/revive
shell-script matrix.
"""

__version__ = "0.1.0"
