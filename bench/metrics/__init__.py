"""Per-layer metric readers, one file each, named as the metric.

Each has `read(ctx) -> float | None`, `ctx` a `bench.trace.Context`; a
reader that finds nothing to read returns None and the harness leaves the
metric out of the result line.
"""
