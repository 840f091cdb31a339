"""Device busy time a round under no ``dppf.`` scope, in ms: ops outside
the local steps' loop and the consensus (the round's own scalars and
copies), and busy time no leaf op covers. With forward, backward, update,
view and local_other (a local step each, times tau) and the consensus it
partitions the busy time (see ``bench/scopes.py``). Max over the cell's
chips."""
from bench.scopes import UNSCOPED, layer_ms


def read(ctx):
    return layer_ms(ctx, UNSCOPED)
