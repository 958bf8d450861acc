"""One reader per metric: ``chipbench/metrics/<name>.py`` defines
``read(rec) -> float | None``, where ``rec`` is the run record that
``chipbench/run.py`` assembles. A reader that finds nothing to read returns
None, and the metric is left out of the result line."""
