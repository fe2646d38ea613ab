"""Host time per batch in the harness's dispatch span (assemble, pad,
copy to the device, enqueue), mean over the traced window, in ms."""


def read(r):
    return 1e3 * sum(r.dispatch_s) / len(r.dispatch_s) if r.dispatch_s else None
