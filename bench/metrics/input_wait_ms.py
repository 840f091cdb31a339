"""Device idle time a round inside the program's ``dppf.batch`` host
spans, in ms: the time the chip waited while the supervisor built the
round's input (see ``bench/scopes.py``). Max over the cell's chips."""
from bench.scopes import span_idle_ms


def read(ctx):
    return span_idle_ms(ctx, "dppf.batch")
