"""A round's share of the HBM roofline: the least time the chip could
take to move the bytes one round must move (``lib/necessary_bytes.py``,
from the configuration's and the cell's numbers alone) over the
device-busy time a round took. The step has no matmul, so
bytes bound it."""

from benchmarks.lib.necessary_bytes import necessary_bytes_per_round
from benchmarks.lib.peaks import peaks_for


def read(obs):
    trace, n = obs["trace"], obs["counters"].get("traced_rounds")
    if not trace or not trace["devices"] or not n or not trace["busy_s"]:
        return None
    necessary = necessary_bytes_per_round(
        obs["config"], obs["workload"]["proposals_per_round"])
    floor_s = necessary / peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (trace["busy_s"] / n)
