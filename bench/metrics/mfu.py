"""Model FLOPs utilisation of a training cell: the window's training
steps times the model FLOPs of every replica's step (`step_flops` of the
cell's problem module: forward and backward, attention's causal half,
routed experts at their expected share) over the traced window times the
cell's chips times the chip's peak, in percent. None for a cell whose
problem counts no model FLOPs."""

from bench import lm_layers


def read(ctx):
    flops = lm_layers.step_flops(ctx)
    if flops is None or not ctx.window_s or not ctx.iterations:
        return None
    replicas = ctx.cell.cfg["backend"]["params"]["mesh"][0]
    return 100.0 * ctx.iterations * flops * replicas / (
        ctx.window_s * ctx.cell.chips * ctx.peak["flops_per_s"])
