"""Share (%) of the traced window in which no operation ran on the
device: 1 - busy / window.  In a replay that is the host's preparation
and the waits between epochs; in a live cell, the host's per-tick
refresh, transfers and dispatch against the device's work."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
