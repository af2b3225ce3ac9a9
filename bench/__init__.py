"""On-chip serving benchmark: one model configuration under one traffic
mix per cell, driven open-loop through ``ContinuousEngine``.

``bench/run.py`` is the entry.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of
its own (``configs/``, ``traffic/``, ``metrics/``) and is found by the
name ``BENCHMARK.json`` gives it.
"""
