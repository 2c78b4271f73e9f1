"""``idle.device``: the share of the traced window in which no operation ran
on the device (1 - busy / window), averaged over the chips used."""


def read(ctx):
    return 100.0 * (1.0 - ctx.summary.busy_s / ctx.summary.window_s)
