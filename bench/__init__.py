"""Chip benchmark of the serving system: one cell (a model configuration
under a traffic mix) per run of ``bench/run.py``.  Everything that belongs
to one configuration, traffic mix or per-layer metric is a data file or a
small reader of its own, found by the name ``BENCHMARK.json`` gives it."""
