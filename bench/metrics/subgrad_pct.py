"""Share of the device's busy time in the problem's subgradient: the ops
whose innermost named scope is `dda.subgrad` (`bench/layers.py`)."""

from bench import layers


def read(ctx):
    return layers.scope_share_pct(ctx, "dda.subgrad")
