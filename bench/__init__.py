"""The chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

See ``bench/run.py`` for what a run does and ``BENCHMARK.json`` for the
cells and metrics.
"""
