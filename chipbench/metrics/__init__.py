"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

``read(run)`` takes the finished run (:class:`chipbench.run.Run`) and
returns the metric's value, or ``None`` where the run holds nothing to
read it from; the harness then leaves the metric out of the line.
"""
