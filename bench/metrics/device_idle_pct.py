"""Share of the traced window in which no operation ran on the device,
mean over the chips, in percent."""


def read(v):
    if v.trace is None:
        return None
    return 100.0 * (1.0 - v.trace.busy_s / v.trace.window_s)
