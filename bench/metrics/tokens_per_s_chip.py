"""Training tokens of all workers in the window's whole rounds
(R * tau * B * S a round), over the window's seconds and the cell's chips."""


def read(ctx):
    if not ctx.window_s:
        return None
    return ctx.window_tokens / ctx.window_s / ctx.chips
