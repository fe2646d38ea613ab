"""Device busy time of the serve step per real (unpadded) row scored in
the traced window, in us."""


def read(r):
    return 1e6 * r.busy_s / r.rows if r.rows and r.busy_s > 0 else None
