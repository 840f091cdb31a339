"""1 - (union of device operation intervals / traced window), in percent,
averaged over the cell's chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.chips or tr.window_ns <= 0:
        return None
    busy = sum(tr.busy_ns(c) for c in tr.chips) / len(tr.chips)
    return 100.0 * (1.0 - busy / tr.window_ns)
