"""device_idle_pct: the share of the traced window in which no operation
ran on the device (1 - busy / window, busy the union of the op intervals
of each chip's trace, averaged over the chips), in %."""


def read(r: dict):
    red = r["red"]
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
