"""How the program starts: no fallback off the chip, one cache place.

An entry point that needs a TPU must FAIL — non-zero exit, nothing on
stdout — when JAX finds none, instead of quietly measuring the CPU
(chip_smoke.py here; benchmarks/run.py in
tests/benchmarks/test_run_cli.py); the compile cache is placed from outside
when ``JAX_COMPILATION_CACHE_DIR`` is set; processes a chip-owning
parent spawns import no JAX. None of these tests compiles a protocol
step: they run in seconds (the chip's own check is chip_smoke.py,
through the chip tool).
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from minpaxos_tpu import deployments

REPO = Path(__file__).resolve().parents[1]


def _run(args, cwd=REPO, timeout=120, **env_over):
    env = dict(os.environ)
    for k, v in env_over.items():  # None = unset
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------ compile cache

def test_compile_cache_dir_placed_from_outside(monkeypatch, tmp_path):
    """Env set -> the function sets NO directory (JAX reads the
    variable itself) and reports the outside one."""
    import jax

    from minpaxos_tpu.utils.backend import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_fixed_checkout_path(monkeypatch):
    """Env unset -> <checkout>/.jax_cache, a path with no temporary
    name, pid or time in it (the directory is part of the cache key)."""
    import jax

    from minpaxos_tpu.utils.backend import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


# --------------------------------------------------------- chip_smoke

def test_chip_smoke_refuses_cpu_before_any_compile():
    t0 = time.monotonic()
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""  # no result line of any kind
    assert "'cpu'" in out.stderr and "nothing was run" in out.stderr
    assert time.monotonic() - t0 < 60


def test_chip_smoke_dry_mode_is_explicit():
    """--dry-cpu without JAX_PLATFORMS=cpu is refused up front: the dry
    mode is an argument plus an explicit platform, never what happens
    when no chip is found."""
    out = _run(["chip_smoke.py", "--dry-cpu"], JAX_PLATFORMS=None)
    assert out.returncode == 2 and out.stdout == ""
    assert "JAX_PLATFORMS=cpu" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the
    repo: non-zero exit, no result."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _run(["chip_smoke.py", "--dry-cpu"], cwd=tmp_path,
               JAX_PLATFORMS="cpu", PYTHONPATH=None)
    assert out.returncode != 0 and out.stdout == ""
    assert "minpaxos_tpu" in out.stderr


def test_chip_smoke_last_line_is_exactly_the_verdict():
    """The chip check reads the LAST stdout line and refuses any key
    beyond ok / device{platform, kind, count}; everything else the
    smoke reports goes on the record line before it. A dry run's last
    line carries no "ok" to be read as a pass."""
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for held in (True, False):
        v = chip_smoke.verdict(held, device, dry=False)
        assert list(v) == ["ok", "device"] and v["ok"] is held
        assert list(v["device"]) == ["platform", "kind", "count"]
    dry = chip_smoke.verdict(True, device, dry=True)
    assert dry["dry"] is True and "ok" not in dry


def test_chip_owner_children_import_no_jax():
    """One process owns the chip: what chip_smoke.py / the soak swarm
    spawn must never initialise a backend of their own."""
    out = _run(["-c", "import sys, minpaxos_tpu.cli.client, "
                "minpaxos_tpu.cli.master, minpaxos_tpu.soak.swarm; "
                "sys.exit('jax' in sys.modules)"])
    assert out.returncode == 0, out.stderr


# -------------------------------------------------------- deployments

def test_headline_config_on_chip_shape():
    """What chip_smoke.py phase A runs on the chip."""
    g, w, p, _k = deployments.TPU_SHAPE
    assert g * w == 1_048_576
    cfg, key_space = deployments.headline_config(True, w, p)
    assert (cfg.n_replicas, cfg.window, cfg.exec_batch) == (5, 4096, 512)
    # catch-up 2p (a revived follower reheals under full load) and a
    # table 4x the key space (no insert lost): PR 21's chip findings
    assert cfg.catchup_rows == 2 * p and cfg.inbox == p + 4 * p + 128
    assert key_space == 1 << 14 and 4 * key_space == 1 << cfg.kv_pow2
    assert cfg.quorum1 == cfg.quorum2 == 3


# ------------------------------------------------------------- server

def test_server_absent_platform_fails_before_registering():
    """-platform is the only selector: an absent platform fails the
    boot loudly, and before the process talks to any master."""
    out = _run(["-m", "minpaxos_tpu.cli.server", "-platform",
                "no_such_platform", "-mport", "1"])
    assert out.returncode != 0
    assert "no_such_platform" in out.stderr
    assert "registered" not in out.stdout


def test_server_flag_builders_compile_bench_tcp_shape():
    """chip_smoke.py phase B composes its replicas from the server
    binary's own flags: the builders must give what main() would."""
    from minpaxos_tpu.cli import server as server_cli

    args = server_cli.build_parser().parse_args(
        ["-min", "-durable", *deployments.SERVER_SHAPE])
    cfg = server_cli.config_from_args(args, 3)
    assert (cfg.n_replicas, cfg.window, cfg.inbox, cfg.exec_batch,
            cfg.kv_pow2) == (3, 2048, 1024, 128, 18)
    flags = server_cli.flags_from_args(args)
    assert flags.durable and flags.warm_variants


# ------------------------------------------------- boot-time repairs

def test_register_returns_once_an_id_is_assigned():
    """A harness registers its replicas one after another: each call
    must return with its id at once, not sit out timeout_s waiting for
    a membership only its own next call can complete."""
    from minpaxos_tpu.runtime.master import Master, register_with_master
    from minpaxos_tpu.utils.netutil import free_ports

    mport = free_ports(1)[0]
    master = Master("127.0.0.1", mport, 3)
    master.start()
    try:
        t0 = time.monotonic()
        ids = [register_with_master(("127.0.0.1", mport), "127.0.0.1",
                                    7000 + i, timeout_s=5.0)
               for i in range(3)]
        assert ids == [0, 1, 2]
        assert time.monotonic() - t0 < 2.0
    finally:
        master.stop()


def test_idle_peer_link_survives_the_dial_timeout():
    """A dialed socket keeps dial_peer's 1 s timeout; a read timing out
    on it is an idle link, not a dead one."""
    from minpaxos_tpu.runtime.transport import Transport
    from minpaxos_tpu.utils.netutil import free_ports

    addrs = [("127.0.0.1", p) for p in free_ports(2)]
    t0, t1 = Transport(0, addrs), Transport(1, addrs)
    try:
        t0.listen()
        t1.listen()
        t1.connect_peers()  # 1 dials 0
        deadline = time.monotonic() + 5.0
        while not (t0.peer_alive(1) and t1.peer_alive(0)):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(1.6)  # > the dial timeout, with no traffic
        assert t0.peer_alive(1) and t1.peer_alive(0)
    finally:
        t0.stop()
        t1.stop()
