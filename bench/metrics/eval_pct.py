"""Share of the device's busy time in the trace evaluation: the ops
outside the scan over iterations (`bench/scopes.py`)."""

from bench import scopes


def read(ctx):
    pred = scopes.outside_iteration(ctx)
    return None if pred is None else ctx.busy_share_pct(pred)
