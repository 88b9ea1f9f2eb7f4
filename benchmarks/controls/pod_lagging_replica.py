"""Control for the pod cell: the reference put in the program's place
with ONE stated guarantee broken — every replica of a group executes
the same committed prefix.

The last replica of every sampled group holds the table a replica that
never executed the final round would hold (committed at a quorum,
applied on it only): the stream replayed without its last round.
``correct`` has to come out false, by ``table_mismatch``.
"""


def apply(evidence: dict) -> dict:
    short = evidence["replay"](evidence["rounds"][:-1])
    tables = {s: [*t[:-1], {k: v & 0xFFFFFFFF for k, v in short[s].items()}]
              for s, t in evidence["tables"].items()}
    return {**evidence, "tables": tables}
