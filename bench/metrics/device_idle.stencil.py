"""Percent of the traced window in which no op ran on the device, in the
stencil cells: 100 * (1 - busy / window)."""


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0 or t.n_devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
