"""Control for the kill / recover cell: the reference put in the
program's place with ONE stated guarantee broken: ``recovery``, a
replica revived after an outage of any length converges to its peers'
table.

The victim of every sampled group holds the table a replica that
stopped at the kill and was never healed would hold, which is what the
pod did before it had a state transfer (a follower beyond retention
stayed frozen for good): the stream replayed up to the kill only.
``correct`` has to come out false, by ``table_mismatch``.
"""


def apply(evidence: dict) -> dict:
    frozen = evidence["replay"](evidence["rounds_before_kill"])
    victim = evidence["victim"]
    tables = {s: [*t[:victim],
                  {k: v & 0xFFFFFFFF for k, v in frozen[s].items()},
                  *t[victim + 1:]]
              for s, t in evidence["tables"].items()}
    return {**evidence, "tables": tables}
