"""Device: share of the traced window in which no operation ran on the
chip (mean over the chips used), in %."""


def read(ctx):
    s = ctx.trace
    if not s or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_mean_s / s.window_s)
