"""Share of the device's busy time in the multi-head latent attention:
the ops whose innermost named scope is `lm.mla` (`bench/lm_layers.py`),
forward, recomputation and backward."""

from bench import lm_layers


def read(ctx):
    return lm_layers.share_pct(ctx, "lm.mla")
