"""Share of the traced window in which no operation ran on the chip:
100 x (1 - union of the device ops' intervals / window)."""


def read(ctx):
    if not ctx.busy_ns or not ctx.window_ns:
        return None
    return 100.0 * (1.0 - ctx.busy_ns / ctx.window_ns)
