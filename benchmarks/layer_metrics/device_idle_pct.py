"""Share of the traced window in which no operation ran on the chip:
1 - union of the device's operation intervals / traced window."""


def read(obs):
    trace = obs["trace"]
    if not trace or not trace["devices"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
