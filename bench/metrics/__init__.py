"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

Each module has ``read(run) -> float | None``; ``run`` is a
``bench.run.RunView``.  A reader that finds nothing to read returns None
and the harness leaves the metric out of the result line.
"""
