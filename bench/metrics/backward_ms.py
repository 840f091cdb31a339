"""Device time a local step of the backward pass, in ms: leaf ops under
``transpose(jvp(dppf.model))``, the recomputation of the attention's
checkpointed chunks included (see ``bench/scopes.py``). Max over the
cell's chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "backward")
