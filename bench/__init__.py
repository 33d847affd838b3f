"""Chip benchmark for the serving path, driven by data.

``BENCHMARK.json`` at the repository root names configurations, traffic
mixes, cells and metrics; each of them is a file of its own under this
directory, found by its name (see :mod:`bench.spec`).  ``bench/run.py``
runs one cell on the chip and prints one JSON line.
"""
