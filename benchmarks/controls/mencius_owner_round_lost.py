"""Control for the Mencius pod cell: the reference put in the program's
place with ONE stated guarantee broken — replication: every replica's
table holds each owner stream's last write per key.

The last replica of every sampled group holds the table of a replica
on which the last owner's final round of commands was never applied
(committed at a quorum of the others, lost here): the streams replayed
with that one owner's last round left out. ``correct`` has to come out
false, by ``table_mismatch``.
"""


def apply(evidence: dict) -> dict:
    rounds, owners = evidence["rounds"], evidence["owners"]
    short = evidence["replay"]([rounds] * (owners - 1) + [rounds[:-1]])
    tables = {s: [*t[:-1], {k: v & 0xFFFFFFFF for k, v in short[s].items()}]
              for s, t in evidence["tables"].items()}
    return {**evidence, "tables": tables}
