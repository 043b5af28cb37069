"""Share of the device's busy time in the MoE FFN: the ops in `lm.moe`
and the scopes nested in it, the router (`lm.moe.route`), the held
experts (`lm.moe.experts`) and the shared experts (`lm.moe.shared`)
(`bench/lm_layers.py`)."""

from bench import lm_layers


def read(ctx):
    return lm_layers.share_pct(ctx, "lm.moe")
