"""Benchmark of the served PaReNTT polymul path on one TPU: the harness
(``run.py``), its traffic, reference, byte counts, trace reduction and
per-layer readers.  ``BENCHMARK.json`` at the checkout root names the
cells; everything a cell needs is found here by name."""
