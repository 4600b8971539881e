"""Prompt tokens served from the prefix cache over all prompt tokens the
engine admitted in the window (engine counters)."""


def read(ctx):
    hit = ctx.stats["prefix_hit_tokens"]
    total = hit + ctx.stats["prefill_tokens"]
    return 100.0 * hit / total if total else None
