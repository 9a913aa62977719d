"""Per-layer metric readers, one module per source. A reader is a function
`(run, **args) -> float | None`; `run` is the harness's record of the
measured run (run.py: Run). A reader that finds nothing to read returns
None and the metric is left out of the result line."""
