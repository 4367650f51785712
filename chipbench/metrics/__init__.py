"""One module per metric (``chipbench/metrics/<name>.py``), found by the
metric's name in ``BENCHMARK.json``.  Each has ``value(run)``, which reads
the metric from a finished :class:`chipbench.harness.Run` and returns a
number, or None where the run holds nothing to read."""
