"""Share of the traced window in which no operation ran on the device,
mean over the chips."""


def read(ctx):
    return 100.0 * ctx.reduced.idle_share if ctx.reduced else None
