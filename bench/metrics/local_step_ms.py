"""Device busy time of the local steps, in ms a step: busy time in the
window outside the consensus kernels and the collectives, over traced
rounds x tau. Max over the cell's chips."""
from bench.metrics.consensus_kernel_ms import KERNELS
from bench.trace import base_name, is_collective, measure, subtract


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.traced_rounds:
        return None
    worst = 0.0
    for c in tr.chips:
        other = tr.intervals(c, lambda op: base_name(op) in KERNELS
                             or is_collective(op))
        worst = max(worst, measure(subtract(tr.intervals(c), other)))
    steps = ctx.traced_rounds * ctx.tau
    return worst / steps / 1e6 if worst > 0 else None
