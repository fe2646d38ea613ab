"""Share of the traced window in which no operation ran on the device
(1 - union of operation intervals / window), mean over the chips, in %."""


def read(r):
    return 100.0 * (1.0 - r.busy_s / r.window_s) if r.window_s > 0 and r.busy_s > 0 else None
