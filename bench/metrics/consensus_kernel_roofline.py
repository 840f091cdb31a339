"""The consensus kernels' share of their HBM roofline, in percent: the
bytes one consensus stage must move (two reads and one write of the padded
(R, width) fp32 view, bench/flops.py) over the kernels' device time a
round, over the chip's HBM bandwidth. The stage's FLOPs are negligible,
so bandwidth bounds it."""
from bench.metrics.consensus_kernel_ms import kernel_ns_per_round


def read(ctx):
    ns = kernel_ns_per_round(ctx)
    if ns is None:
        return None
    least_s = ctx.consensus_bytes_per_round / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
