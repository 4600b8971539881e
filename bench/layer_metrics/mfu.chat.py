"""Useful FLOPs of the traced steps (bench/flops.py: routed tokens times
top-k experts, attention over real contexts, no padding) over their
device time times the chips' bf16 peak."""


def read(ctx):
    return ctx.mfu()
