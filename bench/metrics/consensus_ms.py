"""Device time a round of the consensus, in ms: leaf ops under
``dppf.consensus``, its kernels and exchange included (see
``bench/scopes.py``). Max over the cell's chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "consensus")
