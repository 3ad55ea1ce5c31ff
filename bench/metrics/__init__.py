"""Metric readers, one file per metric, end-to-end and per-layer alike,
found by the metric's name in ``BENCHMARK.json``.  Each ``read(ctx)``
takes the run's ``bench.harness.Context`` and returns a number, or None
when the run gives it nothing to read (the harness then leaves the
metric out)."""
