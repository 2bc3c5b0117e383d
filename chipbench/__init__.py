"""Chip benchmark for BWKM: fit time and quality, and online predict latency.

Run one cell from the root of a checkout::

    python3 -m chipbench.run --workload susy_k27.fit --seed 7 --seconds 30 --trace 0

``BENCHMARK.json`` names the cells; each configuration, traffic mix,
per-layer metric, kernel work count and limit file lives in a file of its
own under this directory and is found by name.
"""
