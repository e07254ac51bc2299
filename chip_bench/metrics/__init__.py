"""One reader per metric, ``metrics/<name>.py``, found by the metric's name
in ``BENCHMARK.json``.  Each has ``read(run) -> float | None``, where ``run``
is a :class:`chip_bench.harness.Run`; ``None`` means the run held nothing
to read, and the metric is left out of the result line.
"""
