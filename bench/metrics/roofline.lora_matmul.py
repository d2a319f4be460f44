"""Roofline share of the fused NF4 LoRA matmul's forward kernel
(``kernels/lora_matmul.py``): the least time its calls could take on the
chip (from their shapes, bench/lib/flops.py) over the time its device
events took. Events are matched by the kernel's op name (``lora_matmul.<n>``)."""
from bench.lib import readers

NAMES = ("lora_matmul",)


def read(x):
    return readers.roofline(x, "lora_matmul", NAMES)
