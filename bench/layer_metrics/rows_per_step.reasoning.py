"""Decode rows per step that had any: the batch the engine kept busy over
the window (engine counters)."""


def read(ctx):
    steps = ctx.stats["decode_steps"]
    return ctx.stats["decode_tokens"] / steps if steps else None
