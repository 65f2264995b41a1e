"""One module per way of reading a per-layer metric out of what a job
observed; ``perfbench/metrics/<metric>.json`` names its reader and gives its
arguments.  Each has ``read(observed, **args) -> float | None`` and returns
None when there is nothing to read."""
