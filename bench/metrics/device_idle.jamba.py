"""Share of the traced window in which no operation ran on the chip."""
from bench.lib import readers


def read(x):
    return readers.idle(x)
