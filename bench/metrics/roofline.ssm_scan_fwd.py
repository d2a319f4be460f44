"""Roofline share of the selective scan's forward kernel
(``kernels/selective_scan.py``, instruction ``ssm_scan_fwd``): its bytes
over the HBM bandwidth (its arithmetic is VPU work, which has no peak in
bench/lib/peaks.py), over the time its device events took."""
from bench.lib import readers

NAMES = ("ssm_scan_fwd",)


def read(x):
    return readers.roofline(x, "ssm_scan_fwd", NAMES)
