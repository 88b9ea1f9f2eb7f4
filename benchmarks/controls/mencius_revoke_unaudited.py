"""Control for the Mencius owner-down cell: the reference put in the
program's place with ONE stated guarantee broken: ``revocation``, the
dead owner's values that a majority accepted commit with their values.

Every live replica of every sampled group holds the table of a program
whose revocation skipped its phase-1 audit and committed no-ops over
the dead owner's turns from its last committed one on: the victim's
commands of the rounds it could not commit before it died (accepted by
every live replica, committed by none) are missing, each of its keys
holding its write from before them. ``correct`` has to come out false,
by ``victim_values_lost`` and ``table_mismatch``.
"""


def apply(evidence: dict) -> dict:
    victim, kill = evidence["victim"], evidence["kill_round"]
    offered = [list(rounds) for rounds in evidence["rounds_of_owner"]]
    offered[victim] = [r for r in offered[victim]
                       if r < kill - evidence["uncommitted_at_kill"]]
    short = evidence["replay"](offered)
    per_owner = evidence["keys_per_owner"]

    def unaudited(table, group):
        kept = {k: v for k, v in table.items() if k // per_owner != victim}
        kept.update({k: v & 0xFFFFFFFF for k, (v, _) in short[group].items()
                     if k // per_owner == victim})
        return kept

    tables = {s: [unaudited(t, s) for t in ts]
              for s, ts in evidence["tables"].items()}
    return {**evidence, "tables": tables}
