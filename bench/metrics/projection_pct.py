"""Share of the device's busy time in the PSD projection's
eigendecomposition: the loops nested inside the scan over iterations,
which are `eigh`'s (`bench/scopes.py`)."""

from bench import scopes


def read(ctx):
    if ctx.iteration_loop is None:
        return None
    pred = scopes.loop_in_iteration(ctx)
    if not ctx.select(pred):
        return None
    return ctx.busy_share_pct(pred)
