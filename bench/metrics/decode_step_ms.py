"""Median host time of ``Engine.step()`` calls that ran decode only, ms."""

from bench.readers import stat


def read(r):
    return stat(r, "decode_step_ms")
