"""device.idle_share (%): 1 - the union of op intervals on chip 0 over the
traced window."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t.busy(0) / t.window_s)
