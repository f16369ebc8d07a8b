"""Model FLOP/s utilisation over the window, % of the chips' bf16 peak."""

from bench.readers import mfu


def read(r):
    return mfu(r)
