"""Device time a round in which a collective (all-gather, all-reduce,
collective-permute, reduce-scatter, all-to-all) is in flight or running
and no other operation runs on that chip, in ms. Max over the cell's
chips; a cell without collectives has nothing to read."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.traced_rounds or len(tr.chips) < 2:
        return None
    worst = max(tr.exposed_collective_ns(c) for c in tr.chips)
    return worst / ctx.traced_rounds / 1e6
