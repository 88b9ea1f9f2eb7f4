"""Share of the slots replica 0's merged Mencius frontier passed in the
window that a revocation (or a takeover) made no-ops, not their owner:
100 x ``revoked_slots`` / (``command_commits`` + ``noop_slots``), all
three counted on the device (``obs.process_pods()``). With one of five
owners down and the others loaded, just under a fifth; 0 without a
fault. A program without the counter reads nothing."""


def read(obs):
    c = obs["counters"]
    revoked = c.get("revoked_slots")
    noops, commands = c.get("noop_slots"), c.get("command_commits")
    if revoked is None or noops is None or commands is None \
            or noops + commands == 0:
        return None
    return 100.0 * revoked / (noops + commands)
