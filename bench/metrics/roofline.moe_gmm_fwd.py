"""Roofline share of the grouped NF4 expert matmul's forward kernel
(``kernels/moe_gmm.py``, instruction ``moe_gmm_fwd``): the least time
its calls could take on the chip, counting the routed rows only (from
their shapes, bench/lib/flops_jamba.py), over the time its device events
took. Rows that pad each expert's group show as lost share."""
from bench.lib import readers

NAMES = ("moe_gmm_fwd",)


def read(x):
    return readers.roofline(x, "moe_gmm_fwd", NAMES)
