"""Share of the traced window in which no program ran on the device; the
reader of every ``device_idle.<cells>`` metric."""


def read(reduced):
    if reduced is None or reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - reduced.busy_s / reduced.window_s)
