"""Share of the traced window in which no op ran on the device, in %
(`idle_share.fred`, `idle_share.lm`)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.red["busy_s"] / ctx.red["window_s"])
