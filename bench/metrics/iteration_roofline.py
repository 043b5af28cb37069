"""One DDA iteration's least time over its measured device time, in
percent.

Least time: the larger of the iteration's least bytes over the chip's
memory bandwidth and its flops over its peak rate, counted from shapes by
the problem module's `iteration_work`. Measured time: the scan over
iterations' device time (its loop ops' durations, all of the loop body),
over the iterations the traced solves ran."""

from bench import roofline


def read(ctx):
    module = ctx.problem_module
    if ctx.iteration_loop is None or not ctx.iterations \
            or not hasattr(module, "iteration_work"):
        return None
    loop_ns = sum(o.end - o.start for o in ctx.ops
                  if o.name == ctx.iteration_loop)
    per_iter_s = loop_ns / 1e9 / ctx.iterations
    flops, bytes_ = module.iteration_work(ctx.cell.cfg, ctx.cell.traffic)
    return 100.0 * roofline.least_seconds(flops, bytes_, ctx.peak) \
        / per_iter_s
