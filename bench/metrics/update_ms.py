"""Device time a local step of the optimizer update, in ms: leaf ops under
``dppf.update`` (learning rate, gradient norm, optimizer step on the flat
view; see ``bench/scopes.py``). Max over the cell's chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "update")
