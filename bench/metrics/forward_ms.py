"""Device time a local step of the forward pass, in ms: leaf ops whose
scope path is under ``jvp(dppf.model)`` and not ``transpose(`` (see
``bench/scopes.py``). Max over the cell's chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "forward")
