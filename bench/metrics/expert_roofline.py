"""The held experts' share of their roofline: the least time their
matrix products take at the chip's bf16 peak (the routed experts' model
FLOPs of the window's steps, `step_flops(cfg, "experts")` of the cell's
problem module, at the expected share of tokens) over the self time of
the ops in `lm.moe.experts` on one chip (dispatch, grouped matmuls and
combine, forward, recomputation and backward), in percent."""

from bench import lm_layers, roofline


def read(ctx):
    flops = lm_layers.step_flops(ctx, "experts")
    seconds = lm_layers.self_s(ctx, "lm.moe.experts")
    if flops is None or seconds is None or not ctx.iterations:
        return None
    least = roofline.least_seconds(ctx.iterations * flops, 0.0, ctx.peak)
    return 100.0 * least / seconds
