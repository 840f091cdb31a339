"""Process start to the first timed round: imports, weights, state,
compilation or cache reads, and the warm-up rounds; the readings taken
for the correctness check are left out."""


def read(ctx):
    return ctx.setup_s
