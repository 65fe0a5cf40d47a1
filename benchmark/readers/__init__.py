"""Per-layer metric readers, found by the `reader` name in metrics/<metric>.json.

Each module has `read(ctx, **args) -> float | None`; one that finds nothing to
read returns None and the harness leaves the metric out of the line. `ctx` is
what the run gathered: the Run, the cell's kind, the job's directory, the
window, the reduced trace (`trace`, with --trace 1), and for serving the
client's records and the polled /stats.
"""
