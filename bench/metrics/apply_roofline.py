"""HBM-roofline share of the one-kernel server apply, in %
(`apply_roofline.fred`, `apply_roofline.lm`).

The bytes the kernel must move for the traced windows (bench/flops.py
`apply_kernel_bytes`, unpadded leaves), over the chip's HBM bandwidth,
over the summed device time of the kernel's launches in the trace.  The
kernel moves about one byte per operation, so bytes bound it.
"""
from bench import flops


def read(ctx):
    return flops.apply_roofline(ctx)
