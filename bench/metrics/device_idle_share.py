"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of the device-op intervals) / (the window)."""


def read(run):
    t = run["trace"]
    if not t["device"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
