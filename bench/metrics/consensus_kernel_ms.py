"""Device time a round of the consensus stage's Pallas kernels, in ms: the
one-pass ``fused_round`` on stacked workers, or ``partial_gram`` and
``mix_shard`` on a column shard. Max over the cell's chips."""
from bench.trace import base_name

KERNELS = ("fused_round", "partial_gram", "mix_shard")


def kernel_ns_per_round(ctx):
    tr = ctx.trace
    if tr is None or not ctx.traced_rounds:
        return None
    per_chip = [tr.op_ns(c, lambda op: base_name(op) in KERNELS)
                for c in tr.chips]
    worst = max(per_chip, default=0.0)
    return worst / ctx.traced_rounds if worst > 0 else None


def read(ctx):
    ns = kernel_ns_per_round(ctx)
    return None if ns is None else ns / 1e6
