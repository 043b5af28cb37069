"""The non-smooth problem's subgradient: its least time over its measured
device time per iteration, in percent.

Least time: every center read once from HBM, n * M * 2 * d float32
bytes, over the chip's memory bandwidth. Measured time: the self time of
the ops in the `dda.subgrad` scope (`bench/layers.py`), over the
iterations the traced solves ran. None for another problem, and where
the trace names no such scope."""

from bench import layers, roofline

F32 = 4


def read(ctx):
    problem = ctx.cell.cfg.get("problem", {})
    found = layers.of(ctx)
    if problem.get("kind") != "nonsmooth" or found is None \
            or not ctx.iterations:
        return None
    scope_ns = ctx.self_ns(lambda o: found.scope(o) == "dda.subgrad")
    if not scope_ns:
        return None
    p = problem["params"]
    bytes_ = F32 * p["n"] * p["M"] * 2 * p["d"]
    least_s = roofline.least_seconds(0.0, bytes_, ctx.peak)
    return 100.0 * least_s / (scope_ns / 1e9 / ctx.iterations)
