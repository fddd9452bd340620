"""The benchmark: harness (`run.py`), jobs, readers and the yardstick (`lib/`).

Cells, metrics and bounds are in `BENCHMARK.json` at the root of the checkout;
everything that belongs to one configuration, traffic mix, job or per-layer
metric is a file of its own under this directory, found by name.
"""
