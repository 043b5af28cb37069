"""Which layer a device op belongs to, from the program's structure.

The trace names each op by its HLO instruction and carries no JAX name
stack, and the program sets no scope of its own yet, so these rules read
what the compiled program's shape makes certain today (`Context` finds
the scan over iterations, `bench/trace.py`):

- the evaluation: every op outside the scan over iterations. It holds
  the trace statistics and a few per-segment ops (PRNG keys, stacking
  the outputs), which took under 0.1 ms a solve on the chip;
- top-k: the `sort` inside the scan, which is how `lax.top_k` lowers on
  the TPU, and the only sort in a `nonsmooth` program (the scatter that
  turns its indices into a mask is not told apart and is left out);
- a projection's eigendecomposition: the loops nested inside the scan
  and everything in them; `eigh` is the only loop in the metric-learning
  iteration.
"""


def outside_iteration(ctx):
    if ctx.iteration_loop is None:
        return None
    return lambda op: not ctx.in_iteration(op)


def sort_in_iteration(ctx):
    return lambda op: op.opcode == "sort" and ctx.in_iteration(op)


def loop_in_iteration(ctx):
    """Ops in a loop nested inside the scan, and those loops."""
    loop = ctx.iteration_loop

    def pred(op):
        if loop not in op.loops:
            return False
        return op.opcode == "while" or op.loops[-1] != loop
    return pred
