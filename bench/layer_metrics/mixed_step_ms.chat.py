"""Device time of a step that prefilled prompt chunks, mean over the
traced steps."""


def read(ctx):
    return ctx.device_ms("mixed")
