"""The host's own time per solve, in ms: the mean over the traced solves
of the harness's `bench.solve` span less the program's `repro.wait`
spans in it, the time the host blocked on the device's results
(`bench/layers.py`)."""

from bench import layers


def read(ctx):
    return layers.host_ms_per_solve(ctx)
