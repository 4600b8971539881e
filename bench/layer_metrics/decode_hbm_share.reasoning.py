"""Bytes the traced decode steps had to read (bench/flops.py: weights
outside the experts, the experts their routing touched, the KV of the
real contexts) over their device time times the chips' HBM bandwidth."""


def read(ctx):
    return ctx.decode_hbm_share()
