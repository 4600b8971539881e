"""Device time of a step that only decoded, mean over the traced steps."""


def read(ctx):
    return ctx.device_ms("decode")
