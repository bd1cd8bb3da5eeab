"""Device busy time (ms) per live tick over the traced window: the union
of the device's operation intervals, over the ticks the window ran."""


def read(ctx):
    red, ticks = ctx["trace"], ctx["traced_ticks"]
    if red is None or red.n_devices == 0 or ticks <= 0:
        return None
    return red.busy_s * 1e3 / ticks
