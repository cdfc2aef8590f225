"""One reader per metric, named as in ``BENCHMARK.json``: ``read(run)``
takes a ``harness.RunRecord`` and returns the value, or None when the run
holds nothing to read."""
