"""CPU tests of the readers of the program's own names in the trace: the
`dda.*` scopes of device ops and the `repro.*` host spans
(`bench/layers.py`, `bench/xplane.py`)."""

from __future__ import annotations

import json
import math
import os
import pathlib
import shutil


from bench import harness, layers, trace, xplane

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"
#: two solves of a small instance without top-k, recorded by a program
#: that named no layer (`record_trace.py`)
UNNAMED = DATA / "trace25_small.xplane.pb"
#: two solves with top-k gossip, recorded by a program that names its
#: layers (`record_topk_trace.py`)
NAMED = DATA / "topk25_small.xplane.pb"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW = ("subgrad_pct", "mix_pct", "compress_pct", "host_ms_per_solve")


def _recorded(path: pathlib.Path, attach=True):
    """The `Context` of a recorded trace, as its recorder built it."""
    expected = json.loads(path.with_suffix(".json").read_text())
    chip_ops, spans = trace.read_xplane(str(path), 1)
    cell = harness.Cell(root=ROOT, name="small", chips=1,
                        cfg=expected["cfg"], traffic=expected["traffic"],
                        limits={}, end_to_end=[], per_layer=[])
    window = harness.Window(seconds=0, solves=2, failed=0,
                            iterations=expected["iterations"], traces=[],
                            errors=[])
    ctx = trace.Context(chip_ops, spans, cell, window, expected["device"])
    if attach:
        layers.attach(ctx, str(path))
    return ctx, expected


def _read(ctx, metric):
    return harness.load_module(ROOT, "metrics", metric).read(ctx)


def test_scope_is_the_innermost_named_one():
    assert layers.scope_of("jit(p)/while/body/dda.mix/dda.compress/"
                           "jit(take_along_axis)/gather:") == "dda.compress"
    assert layers.scope_of("jit(p)/while/body/dda.update/mul") == \
        "dda.update"
    assert layers.scope_of("jit(<unknown>)/while/body/closed_call/gather") \
        == ""
    assert layers.scope_of("") == ""
    assert layers.compiler_made("jit(p)/while/body/closed_call/while:")
    assert layers.compiler_made("")
    assert not layers.compiler_made("jit(p)/while/body/dda.mix/add:")


def test_xplane_reader_agrees_with_profile_data():
    """Every event of every plane, with `ProfileData`'s name, start and
    end; the device ops carry a name stack."""
    from jax.profiler import ProfileData

    mine = xplane.read(str(UNNAMED))
    n = 0
    for plane in ProfileData.from_file(str(UNNAMED)).planes:
        for line in plane.lines:
            got = mine[plane.name][line.name]
            want = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            assert [(e.name, e.start, e.end) for e in got] == want
            n += len(want)
    ops = mine["/device:TPU:0"][trace.OPS_LINE]
    assert n > len(ops) > 0
    assert sum(e.tf_op.startswith("jit(") for e in ops) > 0.9 * len(ops)


def test_recording_without_names_reduces_as_before():
    """A trace of a program that names no layer: the new readers find
    nothing, and the named breakdown is the old one, name for name."""
    ctx, expected = _recorded(UNNAMED)
    assert all(_read(ctx, m) is None for m in NEW)
    assert layers.breakdown(ctx) == ctx.breakdown() == expected["breakdown"]
    assert layers.idle_gaps(ctx) == ctx.idle_gaps()


def _synthetic():
    """One solve from 0 to 100 ns: build (0-10), compile (10-20), an
    execute (20-70) holding dispatch (20-30) and wait (30-70), assemble
    (75-95); device ops in dda.subgrad (25-45), dda.mix (45-50) and one
    op of no scope (55-65)."""
    op = trace.Op
    ops = [op("%s", "", "fusion", "", 25, 45),
           op("%m", "", "fusion", "", 45, 50),
           op("%u", "", "copy", "", 55, 65)]
    cell = harness.Cell(root=ROOT, name="c", chips=1, cfg={}, traffic={},
                        limits={}, end_to_end=[], per_layer=[])
    ctx = trace.Context([ops], [(0, 100)], cell, None, CPU)
    spans = [("repro.build", 0, 10), ("repro.compile", 10, 20),
             ("repro.execute", 20, 70), ("repro.dispatch", 20, 30),
             ("repro.wait", 30, 70), ("repro.assemble", 75, 95)]
    layers.attach(ctx, layers.Layers(
        {("%s", 25): "jit(p)/dda.subgrad/sub",
         ("%m", 45): "jit(p)/dda.mix/add", ("%u", 55): ""}, spans))
    return ctx


def test_idle_gaps_are_named_by_program_spans():
    ctx = _synthetic()
    gaps = dict(layers.idle_gaps(ctx))
    # 0-25: build 10, compile 10, dispatch 5 (named at each piece's
    # midpoint: the gap is cut only where solves start or end)
    assert math.isclose(gaps.pop("solve: repro.compile"), 25e-9)
    assert math.isclose(gaps.pop("solve: repro.wait"), 5e-9)       # 50-55
    assert math.isclose(gaps.pop("solve: repro.assemble"), 35e-9)  # 65-100
    assert gaps == {}


def test_scope_shares_and_host_time_of_a_synthetic_solve():
    ctx = _synthetic()
    assert ctx.busy_ns == 35
    assert math.isclose(layers.scope_share_pct(ctx, "dda.subgrad"),
                        100 * 20 / 35)
    assert math.isclose(layers.scope_share_pct(ctx, "dda.mix"), 100 * 5 / 35)
    assert layers.scope_share_pct(ctx, "dda.compress") is None
    # the solve's 100 ns less the 40 ns of its wait
    assert math.isclose(layers.host_ms_per_solve(ctx), 60e-6)
    key = layers.breakdown(ctx)["device_ops"][0][0]
    assert key == "dda.subgrad %s fusion "
    cov = layers.coverage(ctx)
    assert math.isclose(cov["scoped_busy_pct"], 100 * 25 / 35)


def test_new_readers_leave_nothing_on_the_cpu(tmp_path):
    """On the CPU the trace has no device plane: the new readers find
    nothing to read and the harness leaves them out."""
    from bench.tests import test_bench_harness as h
    root = h.make_root(tmp_path, {"nonsmooth.tiny.topk":
                                  h.CELLS["nonsmooth.tiny.topk"]},
                       per_layer={m: None for m in NEW})
    out = h.run(root, "nonsmooth.tiny.topk", trace_on=True)
    assert out["metrics"] == {}
    assert out["correct"]


def test_trace_is_found_in_the_harness_directory(tmp_path, monkeypatch):
    """Readers get the context alone: its trace is the file under the
    temporary directory's `bench_trace_*` whose ops are the context's,
    newer files of other runs notwithstanding."""
    ctx, _ = _recorded(NAMED, attach=False)
    other, _ = _recorded(UNNAMED, attach=False)
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    assert layers.of(other) is None  # no trace directory at all
    for name, src in (("bench_trace_a", NAMED), ("bench_trace_b", UNNAMED)):
        d = tmp_path / name / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(src, d / "host.xplane.pb")
    os.utime(tmp_path / "bench_trace_a" / "plugins" / "profile" / "1" /
             "host.xplane.pb", (1, 1))
    found = layers.of(ctx)
    assert found is not None and found.holds(ctx.ops)
    assert _read(ctx, "subgrad_pct") > 0


def test_new_readers_on_a_recorded_chip_trace():
    """Two solves with top-k traced on a v5e chip: the readers, new and
    old, give what they gave there, and every layer of the program but
    the projection (the problem has none) shows."""
    ctx, expected = _recorded(NAMED)
    for m, want in expected["metrics"].items():
        got = _read(ctx, m)
        assert got > 0 and math.isclose(got, want, rel_tol=1e-12), (m, got)
    assert all(_read(ctx, m) < 100 for m in NEW if m.endswith("_pct"))
    assert layers.breakdown(ctx) == expected["breakdown"]
    shares = layers.coverage(ctx)["scope_pct"]
    assert {"dda.subgrad", "dda.mix", "dda.compress", "dda.update",
            "dda.evaluate"} <= set(shares)
    # the compressor's layer holds top-k's sort
    assert _read(ctx, "compress_pct") > _read(ctx, "topk_sort_pct")


def test_scopes_cover_the_programs_own_ops_in_its_loops():
    """Inside the loops, every op that the program traced is in a
    `dda.*` scope; the rest the compiler made without metadata of their
    own."""
    ctx, _ = _recorded(NAMED)
    cov = layers.coverage(ctx)
    assert cov["scoped_own_loop_pct"] >= 99.0
    assert cov["scoped_busy_pct"] + cov["compiler_made_busy_pct"] >= 99.0


def test_evaluate_scope_agrees_with_eval_pct():
    """`dda.evaluate` against the older reader of the same layer, which
    takes every op outside the scan over iterations."""
    ctx, _ = _recorded(NAMED)
    assert abs(layers.scope_share_pct(ctx, "dda.evaluate")
               - _read(ctx, "eval_pct")) <= 1.0


def test_idle_time_in_solves_is_named_by_program_spans():
    ctx, _ = _recorded(NAMED)
    gaps = dict(layers.breakdown(ctx)["idle_gaps"])
    assert {"solve: repro.build", "solve: repro.assemble"} <= set(gaps)
    assert layers.coverage(ctx)["named_idle_pct"] >= 90.0
