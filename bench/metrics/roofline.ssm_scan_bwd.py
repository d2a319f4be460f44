"""Roofline share of the selective scan's backward kernel
(``kernels/selective_scan.py``, instruction ``ssm_scan_bwd``): its bytes
over the HBM bandwidth (VPU arithmetic, no peak in bench/lib/peaks.py),
over the time its device events took."""
from bench.lib import readers

NAMES = ("ssm_scan_bwd",)


def read(x):
    return readers.roofline(x, "ssm_scan_bwd", NAMES)
