"""On-chip benchmark of the time-range temporal k-core query service.

Run one cell from the repository root::

    python3 -m tcqbench --workload mathoverflow.adhoc --seed 1 --seconds 50 --trace 0

The cells, metrics and bounds are in ``BENCHMARK.json``; each configuration,
traffic mix and per-layer metric is a file of its own (see ``registry``).
"""
