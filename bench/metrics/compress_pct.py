"""Share of the device's busy time in compressed gossip's compressor: the
ops whose innermost named scope is `dda.compress`, which holds top-k's
sort and the scatter of its mask, the compressed messages and the
error-feedback residual (`bench/layers.py`)."""

from bench import layers


def read(ctx):
    return layers.scope_share_pct(ctx, "dda.compress")
