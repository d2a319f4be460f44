"""The window's required matrix-product operations (bench/lib/
flops_jamba.py, from shapes: routed expert rows only, no remat, no scan)
over the traced window and the chip's bf16 peak."""
from bench.lib import readers


def read(x):
    return readers.mfu(x)
