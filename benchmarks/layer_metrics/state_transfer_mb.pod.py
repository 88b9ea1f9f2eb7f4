"""Megabytes the window's state transfers had to move: the installs the
pod counted on the device (``state_transfers`` in
``ShardedCluster.resident_tiers()``: a follower below its leader's
window adopting the leader's executed state) times what one install
must move, from the configuration's numbers alone
(``lib/transfer_bytes.py``: the KV table read once and written once).
One install a group in the kill / recover cell, none in a window
without a fault; a program without the counter reads nothing."""

from benchmarks.lib.transfer_bytes import transfer_bytes_per_install


def read(obs):
    installs = obs["counters"].get("state_transfers")
    if installs is None:
        return None
    return installs * transfer_bytes_per_install(obs["config"]) / 1e6
