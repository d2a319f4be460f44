"""Roofline share of the backward dx kernel of the fused LoRA matmul
(``kernels/lora_matmul.quant_matmul_t``): the least time its calls could
take on the chip over the time its device events took. Events are
matched by the kernel's name."""
from bench.lib import readers

NAMES = ("quant_matmul_t",)


def read(x):
    return readers.roofline(x, "quant_matmul_t", NAMES)
