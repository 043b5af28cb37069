"""Share of the device's busy time in the consensus mix: the ops whose
innermost named scope is `dda.mix`, which holds the neighbour gather, the
Pallas gossip and compress-mix kernels and the dense matmul, and not the
compressor's own work inside it (`bench/layers.py`)."""

from bench import layers


def read(ctx):
    return layers.scope_share_pct(ctx, "dda.mix")
