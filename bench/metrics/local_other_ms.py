"""Device time a local step in ops that the compiler made inside the local
steps' loop (``dppf.local``) with no layer's name, in ms: on the chip the
whole view's fp32-to-bf16 cast, the embedding gradient's scatter and
layout copies (see ``bench/scopes.py``). Max over the cell's chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "local")
