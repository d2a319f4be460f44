"""Roofline share of the grouped NF4 expert matmul's input-gradient
kernel (``kernels/moe_gmm.moe_gmm_t``, instruction ``moe_gmm_dx``): the
least time its calls could take, routed rows only, over the time its
device events took."""
from bench.lib import readers

NAMES = ("moe_gmm_dx",)


def read(x):
    return readers.roofline(x, "moe_gmm_dx", NAMES)
