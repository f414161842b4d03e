"""device_idle_share: share of the traced window with no op on the device (%).

One minus the union of device-op intervals over the window, per chip on
average.
"""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
