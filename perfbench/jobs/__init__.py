"""One module per kind of job; a traffic file names its module under
``job``.  Each has ``run(ctx) -> dict``."""
