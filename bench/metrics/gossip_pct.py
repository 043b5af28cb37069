"""Share of the device's busy time in the consensus mix of the replicas'
parameters: the ops in the fused step's `lm.gossip` scope, the
all-gathers and the mixing product (`bench/lm_layers.py`)."""

from bench import lm_layers


def read(ctx):
    return lm_layers.share_pct(ctx, "lm.gossip")
