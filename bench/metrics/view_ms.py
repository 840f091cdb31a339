"""Device time a local step of the flat view, in ms: leaf ops under
``dppf.view``, both directions (the rows' slices, reshapes and casts into
the model's tree, and under the gradient their transposes back into the
flat rows; see ``bench/scopes.py``). Max over the cell's chips."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "view")
