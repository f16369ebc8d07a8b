"""Device idle share of the traced window, %."""

from bench.readers import idle_share


def read(r):
    return idle_share(r)
