"""One module per metric, named as in BENCHMARK.json.

Each has ``read(ctx) -> float | None``. ``ctx`` is the run record that
``bench/run.py`` builds (``bench.run.RunRecord``): host-clock readings of
the window, the reduced trace of a traced run (``bench.trace.Trace``),
the shape-derived counts of ``bench/flops.py`` and the device's peaks from
``bench/peaks.json``. A reader that finds nothing to read returns None and
the metric is left out of the result line.
"""
import importlib


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read
