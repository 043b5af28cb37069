"""CPU tests of the `subgrad_roofline` reader on the small chip traces
recorded under `bench/tests/data/`."""

from __future__ import annotations

import math

from bench import harness, layers
from bench.tests.test_layers import NAMED, ROOT, UNNAMED, _read, _recorded


def test_reading_of_a_recorded_chip_trace():
    """Two solves of n=64, M=8, d=1024 with top-k on a v5e chip: the
    centres' bytes at 819 GB/s over the `dda.subgrad` scope's time per
    iteration, positive and under 100%."""
    ctx, expected = _recorded(NAMED)
    got = _read(ctx, "subgrad_roofline")
    found = layers.of(ctx)
    scope_s = ctx.self_ns(lambda o: found.scope(o) == "dda.subgrad") / 1e9
    least_s = 4 * 64 * 8 * 2 * 1024 / 819e9
    assert math.isclose(got, 100 * least_s / (scope_s / 100), rel_tol=1e-12)
    assert 0 < got < 100
    # the same time as the scope's share of the busy time
    assert math.isclose(scope_s, expected["metrics"]["subgrad_pct"] / 100
                        * expected["busy_s"], rel_tol=1e-9)


def test_nothing_to_read_is_none():
    """Another problem, or a trace that names no scope, reads None."""
    ctx, _ = _recorded(NAMED)
    ctx.cell = harness.Cell(
        root=ROOT, name="ml", chips=1,
        cfg={**ctx.cell.cfg, "problem": {"kind": "metric_learning",
                                         "params": {"n": 6}}},
        traffic=ctx.cell.traffic, limits={}, end_to_end=[], per_layer=[])
    assert _read(ctx, "subgrad_roofline") is None
    unnamed, _ = _recorded(UNNAMED)
    assert _read(unnamed, "subgrad_roofline") is None
