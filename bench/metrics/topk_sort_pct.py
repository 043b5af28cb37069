"""Share of the device's busy time in the sort of compressed gossip's
top-k selection, which is how `lax.top_k` lowers on the TPU
(`bench/scopes.py`). The scatter that turns the sort's indices into a
mask belongs to the same layer but is not told apart in the trace, so
this reads the sort alone."""

from bench import scopes


def read(ctx):
    pred = scopes.sort_in_iteration(ctx)
    if not ctx.select(pred):
        return None
    return ctx.busy_share_pct(pred)
