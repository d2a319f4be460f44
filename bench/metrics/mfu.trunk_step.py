"""The window's required operations (bench/lib/flops.py, from shapes)
over the traced window and the chip's bf16 peak."""
from bench.lib import readers


def read(x):
    return readers.mfu(x)
