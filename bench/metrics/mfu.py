"""The whole step's share of the chip's bf16 peak, in % (`mfu.fred`,
`mfu.lm`): the operations the model requires per unit of work
(bench/flops.py; no recomputation, no duplicate stale-offset GEMM) times
the measured window's rate, over the peak."""


def read(ctx):
    return 100.0 * ctx.flops_per_unit * ctx.rate / ctx.peaks["bf16_flops"]
