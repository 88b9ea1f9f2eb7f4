"""Control for the served cells: the reference put in the program's
place with ONE stated guarantee broken — an acknowledged write is on
EVERY replica after quiesce.

The last replica holds what a quorum-only system leaves on the replica
that was not in the quorum: its file ends before the first record that
holds any of the log's last ``MISSING`` acknowledged commands (nobody
waited for it, nothing caught it up), and its table lacks what those
rows wrote. ``correct`` has to come out false, by ``log_divergence``
and, where a PUT is among those rows, ``table_mismatch``.
"""

from benchmarks.lib.served_check import client_rows, durable_logs, replay

MISSING = 32  # acknowledged commands the lagging replica never got


def apply(evidence: dict) -> dict:
    files, tables = list(evidence["files"]), list(evidence["tables"])
    whole = durable_logs(files[-1:], evidence["fsyncs"][-1:])[0]
    lost = client_rows(whole["rows"])["cmd_id"][-MISSING:]
    files[-1] = files[-1][:min(whole["first_end"][int(c)] for c in lost) - 1]
    short = durable_logs(files[-1:], evidence["fsyncs"][-1:])[0]
    _, tables[-1] = replay(client_rows(short["rows"]))
    return {**evidence, "files": files, "tables": tables}
