"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in percent."""


def read(name, r):
    t = r["trace"]
    if not t["window_s"] or not t["n_ops"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
