"""CPU tests of the benchmark harness at tiny sizes.

Each test builds a root of its own (a `BENCHMARK.json` and the files the
harness finds by name) and runs the harness's cell runner with the chip
check skipped; the program under test is the repository's.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import check, harness, roofline, trace

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

NONSMOOTH = {
    "name": "nonsmooth_tiny",
    "problem": {"kind": "nonsmooth", "params": {"n": 16, "M": 5, "d": 256}},
    "topology": {"kind": "expander", "params": {"k": 4, "seed": 0}},
    "mixing": {"shifts": [1, 15, 4, 12], "self_weight": 0.2,
               "edge_weight": 0.2},
    "stepsize": {"kind": "sqrt", "params": {"A": 0.004, "q": 0.5}},
    "r": 0.01, "backend": {"kind": "dense", "params": {}},
    "precision": "float32", "matmul_precision": None,
    "control": {"dtype": "bfloat16", "matmul_precision": None},
}
METRIC = {
    "name": "metric_tiny",
    "problem": {"kind": "metric_learning",
                "params": {"n": 6, "m_pairs": 600, "d_feat": 16}},
    "topology": {"kind": "complete", "params": {}},
    "mixing": {"complete": True},
    "stepsize": {"kind": "sqrt", "params": {"A": 0.0004, "q": 0.5}},
    "r": 0.0293, "backend": {"kind": "dense", "params": {}},
    "precision": "float32", "matmul_precision": "highest",
    "control": {"dtype": "float32", "matmul_precision": "high"},
}


def _traffic(name, T, every, compression=None):
    return {"name": name, "loop": "closed", "clients": 1,
            "schedule": {"kind": "every"}, "T": T, "eval_every": every,
            "compression": compression}


TOPK = {"kind": "topk", "params": {"keep": 0.125}}

#: tiny stand-ins of the benchmark's cells: (config, traffic, the cell
#: whose limits they are held to)
CELLS = {
    "nonsmooth.tiny.trace": (NONSMOOTH, _traffic("t_trace", 40, 10),
                             "nonsmooth.expander.trace25"),
    "nonsmooth.tiny.topk": (NONSMOOTH, _traffic("t_topk", 40, 40, TOPK),
                            "nonsmooth.expander.topk_final"),
    "metric.tiny.trace": (METRIC, _traffic("t_metric", 20, 10),
                          "metric_learning.complete.trace10"),
}


def make_root(tmp: pathlib.Path, cells=CELLS, per_layer=()) -> pathlib.Path:
    """A benchmark root holding `cells`, the harness's problem modules
    and peaks, and the per-layer metrics `per_layer` (name -> reader
    source, or None for one of the benchmark's own readers)."""
    bench = tmp / "bench"
    for group in ("problems", "peaks"):
        shutil.copytree(BENCH / group, bench / group)
    for group in ("configs", "traffic", "limits", "metrics"):
        (bench / group).mkdir(parents=True, exist_ok=True)
    configs, workloads = {}, []
    for name, (cfg, traffic, limits_of) in cells.items():
        (bench / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
        (bench / "traffic" / f"{traffic['name']}.json").write_text(
            json.dumps(traffic))
        shutil.copy(BENCH / "limits" / f"{limits_of}.json",
                    bench / "limits" / f"{name}.json")
        configs[cfg["name"]] = {
            "name": cfg["name"], "source": "https://arxiv.org/abs/1209.1076",
            "file": f"bench/configs/{cfg['name']}.json", "reduced": [],
            "why": "tiny"}
        workloads.append({"name": name, "config": cfg["name"],
                          "traffic": traffic["name"], "chips": 1,
                          "why": "tiny"})
    metrics = []
    for mname, source in dict(per_layer).items():
        path = bench / "metrics" / f"{mname}.py"
        if source is None:
            shutil.copy(BENCH / "metrics" / f"{mname}.py", path)
        else:
            path.write_text(source)
        metrics.append({"name": mname, "unit": "%", "better": "lower",
                        "source": "device_trace", "layer": "test",
                        "moves": "iters_per_s"})
    bench_json = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1, "configs": list(configs.values()),
        "workloads": workloads,
        "end_to_end": [
            {"name": "iters_per_s", "unit": "iter/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": metrics}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench_json))
    return tmp


def run(root, name, seed=3, trace_on=False, seconds=0.3):
    import time

    import jax
    cell = harness.load_cell(name, root)
    jax.config.update("jax_default_matmul_precision",
                      cell.cfg["matmul_precision"])
    try:
        return harness.run_cell(cell, seed, seconds, trace_on,
                                time.perf_counter(), CPU)
    finally:
        jax.config.update("jax_default_matmul_precision", None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


# -- the plain reference against the program --------------------------------


@pytest.mark.parametrize("name", sorted(CELLS))
def test_program_matches_reference(root, name):
    out = run(root, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out["metrics"]) == ["iters_per_s", "setup_s"]
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails(name):
    """The reference computed in the precision below the configuration's
    (the control) reads above the limits that sound runs meet."""
    import jax
    cfg, traffic, limits_of = CELLS[name]
    if cfg["control"]["dtype"] == "float32" and jax.default_backend() == "cpu":
        pytest.skip("the CPU computes every float32 matrix product in "
                    "float32, 'high' included; bench/control.py runs this "
                    "control on the chip")
    limits = json.loads((BENCH / "limits" / f"{limits_of}.json").read_text())
    module = harness.load_module(ROOT, "problems", cfg["problem"]["kind"])
    ref = check.reference_trace(module, cfg, traffic, 5, "float32",
                                "highest")
    ctl = check.reference_trace(module, cfg, traffic, 5,
                                cfg["control"]["dtype"],
                                cfg["control"]["matmul_precision"])
    gaps = {f"{k}_rel_gap": check.rel_gap(ctl[k], ref[k]) for k in ref}
    assert any(gaps[k] > limits[k] for k in gaps), (gaps, limits)


def test_control_reads_a_dda_cell(root):
    """`bench/control.py` reads a DDA cell through the same hooks as the
    harness: the program's numbers, the control's, and on a circulant
    graph the gaps of the neighbour-by-neighbour reference."""
    from bench import control
    cell = harness.load_cell("nonsmooth.tiny.trace", root)
    lines = []
    summary = control.read(cell, [4], lambda text: lines.append(
        json.loads(text)))
    line = lines[0]
    assert lines[-1] == summary and summary["seeds"] == [4]
    assert set(line["program"]) == set(check.NUMBERS)
    assert set(line["control"]) == {"fbar_rel_gap", "fxbar_rel_gap",
                                    "disagreement_rel_gap"}
    assert line["program"]["fbar_rel_gap"] < 1e-5
    assert line["control"]["fbar_rel_gap"] > 1e-5
    assert set(summary["order"]) == set(line["control"])
    assert line["ref_final_F"] == pytest.approx(line["final_F"], rel=1e-5)


# -- faults planted in the timed path must come out as not correct ----------


def _zero_subgradient(monkeypatch):
    """A step that leaves the state as it was: no subgradient, so z, x
    and the running average stay at their start."""
    from repro.experiments import runner
    real = runner._build_problem

    def build(spec):
        p = real(spec)
        return dataclasses.replace(
            p, subgrad_stack=lambda x, t, key: x * 0.0)
    monkeypatch.setattr(runner, "_build_problem", build)


def _half_the_nodes(monkeypatch):
    """F averaged over half of the nodes' data, the rest left out."""
    import jax.numpy as jnp
    from repro.experiments import components, runner
    real = runner._build_problem

    def build(spec):
        p = real(spec)
        q = spec.problem.params
        C = jnp.asarray(components.nonsmooth_centers(
            q["n"], q["M"], q["d"], q["seed"]))[: q["n"] // 2]

        def objective(x):
            diff = x[None, None, None, :] - C
            s = jnp.sum(diff * diff, axis=-1)
            return jnp.mean(jnp.sum(jnp.max(s, axis=-1), axis=-1))
        return dataclasses.replace(p, objective=objective)
    monkeypatch.setattr(runner, "_build_problem", build)


def _no_exchange(monkeypatch):
    """The gossip between nodes left out: every mix returns z."""
    from repro.core import consensus
    from repro.kernels import ops
    monkeypatch.setattr(ops, "gossip_gather_mix_impl",
                        lambda z, *a, **k: z)
    monkeypatch.setattr(ops, "compress_mix_impl", lambda z, *a, **k: z)
    monkeypatch.setattr(consensus, "mix_dense", lambda z, P: z)


def _altered_answer(monkeypatch):
    """The last F of each trace altered where the trace is assembled."""
    from repro.core.dda import DDASimulator
    real = DDASimulator._assemble_trace

    def assemble(self, *a, **k):
        tr = real(self, *a, **k)
        tr.fvals[-1] *= 1.001
        return tr
    monkeypatch.setattr(DDASimulator, "_assemble_trace", assemble)


def _no_error_feedback(monkeypatch):
    """Top-k gossip with its error feedback turned off: what a message
    leaves out is dropped instead of carried into the next round."""
    import repro.compress as compress
    real = compress.build_compressor

    def build(kind, params):
        return dataclasses.replace(real(kind, params), error_feedback=False)
    monkeypatch.setattr(compress, "build_compressor", build)


FAULTS = {"state_unchanged": _zero_subgradient,
          "half_the_batch": _half_the_nodes,
          "no_exchange": _no_exchange,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["nonsmooth.tiny.trace",
                                  "nonsmooth.tiny.topk"])
def test_fault_is_not_correct(root, name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(root, name)
    assert out["correct"] is False, out["checks"]


def test_error_feedback_off_is_not_correct(root, monkeypatch):
    """The top-k cell compares F alone; F has to catch a compressor that
    drops its error feedback."""
    _no_error_feedback(monkeypatch)
    out = run(root, "nonsmooth.tiny.topk")
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "no_exchange",
                                   "altered_answer"])
def test_fault_is_not_correct_metric_learning(root, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(root, "metric.tiny.trace")
    assert out["correct"] is False, out["checks"]


# -- the harness runs on data ------------------------------------------------


def test_new_cell_and_metric_are_files_only(tmp_path):
    """A configuration, a traffic mix and a per-layer metric that exist
    only as files of a root are found by name and run."""
    cfg = dict(NONSMOOTH, name="nonsmooth_other",
               problem={"kind": "nonsmooth",
                        "params": {"n": 32, "M": 3, "d": 128}},
               mixing={"shifts": [1, 31, 7, 25], "self_weight": 0.2,
                       "edge_weight": 0.2})
    cells = {"other.cell": (cfg, _traffic("t_other", 30, 15),
                            "nonsmooth.expander.trace25")}
    reader = ("def read(ctx):\n"
              "    return float(ctx.window.solves)\n")
    root = make_root(tmp_path, cells, per_layer={"solves_seen": reader})
    out = run(root, "other.cell", trace_on=True)
    assert out["correct"], out["checks"]
    assert out["metrics"] == {"solves_seen": {
        "value": float(out["attempted"]), "unit": "%"}}
    assert set(out["device"]) >= {"busy_s", "window_s", "memory_peak_bytes"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_reader_that_finds_nothing_is_left_out(tmp_path):
    root = make_root(tmp_path, {k: CELLS[k] for k in
                                ["nonsmooth.tiny.trace"]},
                     per_layer={"eval_pct": None, "device_idle_pct": None})
    out = run(root, "nonsmooth.tiny.trace", trace_on=True)
    # the CPU's trace has no device plane: nothing to read
    assert out["metrics"] == {}
    assert out["correct"]


# -- the trace reduction -----------------------------------------------------


RECORDED = BENCH / "tests" / "data" / "trace25_small.xplane.pb"


def test_self_times_and_union():
    ops = [trace.Op("%outer", "", "while", "", 0, 100),
           trace.Op("%inner", "", "fusion", "", 10, 40),
           trace.Op("%next", "", "fusion", "", 120, 150)]
    trace._nest(ops)
    assert [o.self_ns for o in ops] == [70, 30, 30]
    assert [o.loops for o in ops] == [(), ("%outer",), ()]
    total, merged = trace.union_ns([(o.start, o.end) for o in ops])
    assert total == 130 and merged == [[0, 100], [120, 150]]


def test_idle_gaps_are_named_by_harness_spans():
    ops = [trace.Op("%x", "", "fusion", "", 20, 50),
           trace.Op("%y", "", "fusion", "", 60, 80)]
    cell = harness.Cell(root=ROOT, name="c", chips=1, cfg={}, traffic={},
                        limits={}, end_to_end=[], per_layer=[])
    ctx = trace.Context([ops], [(0, 90), (100, 200)], cell, None, CPU)
    gaps = dict(ctx.idle_gaps())
    assert ctx.window_ns == 200 and ctx.busy_ns == 50
    assert math.isclose(gaps["solve: host before its first device op"],
                        20e-9)
    assert math.isclose(gaps["solve: host between device ops"], 10e-9)
    assert math.isclose(gaps["solve: host after its last device op"], 10e-9)
    assert math.isclose(gaps["harness: between solves"], 10e-9)
    assert math.isclose(gaps["solve: no device op in the solve"], 100e-9)


def test_layers_are_found_by_loop_structure():
    """One solve of T=20 traced every 10: a loop over segments holding the
    scan over iterations (run twice), which holds three runs of a nested
    loop (a problem's own, like eigh) and a sort; evaluation ops sit
    between the scans."""
    from bench import scopes
    op = trace.Op
    ops = [op("%segments", "", "while", "", 0, 100),
           op("%eval.1", "", "fusion", "", 46, 50),
           op("%eval.2", "", "fusion", "", 96, 100)]
    for a in (0, 50):
        ops.append(op("%iterations", "", "while", "", a, a + 45))
        ops.append(op("%sort.1", "", "sort", "", a + 1, a + 5))
        for b in (10, 20, 30):
            ops.append(op("%eigh", "", "while", "", a + b, a + b + 8))
            ops.append(op("%qr", "", "custom-call", "Qr", a + b + 1,
                          a + b + 5))
    cell = harness.Cell(root=ROOT, name="c", chips=1, cfg={},
                        traffic={"T": 20, "eval_every": 10}, limits={},
                        end_to_end=[], per_layer=[])
    ctx = trace.Context([ops], [(0, 100)], cell, None, CPU)
    assert ctx.iteration_loop == "%iterations"
    assert ctx.busy_ns == 100
    # the two evaluations and the segment loop's own time between them
    assert ctx.self_ns(scopes.outside_iteration(ctx)) == 4 + 4 + 2
    assert ctx.self_ns(scopes.sort_in_iteration(ctx)) == 8
    # the nested loops with what they hold: 6 x 8
    assert ctx.self_ns(scopes.loop_in_iteration(ctx)) == 48


def test_reduction_of_a_recorded_chip_trace():
    """Two solves of a small instance traced on a v5e chip
    (`bench/tests/record_trace.py`): the readers give what they gave
    there, and the counts the trace holds are those the solves made."""
    from bench import scopes
    expected = json.loads(RECORDED.with_suffix(".json").read_text())
    chip_ops, spans = trace.read_xplane(str(RECORDED), 1)
    cell = harness.Cell(root=ROOT, name="small", chips=1,
                        cfg=expected["cfg"], traffic=expected["traffic"],
                        limits={}, end_to_end=[], per_layer=[])
    window = harness.Window(seconds=0, solves=2, failed=0,
                            iterations=expected["iterations"], traces=[],
                            errors=[])
    ctx = trace.Context(chip_ops, spans, cell, window, expected["device"])
    assert len(spans) == 2
    assert 0 < ctx.busy_s < ctx.window_s
    assert math.isclose(ctx.busy_s, expected["busy_s"], rel_tol=1e-12)
    # the scan over iterations runs once per segment of each solve, and
    # the Pallas gossip kernel once per iteration inside it
    assert ctx.iteration_loop is not None
    loops = ctx.select(lambda o: o.name == ctx.iteration_loop)
    assert len(loops) == 2 * expected["traffic"]["T"] // \
        expected["traffic"]["eval_every"]
    kernel = ctx.select(lambda o: o.target == "tpu_custom_call")
    assert len(kernel) == expected["iterations"]
    assert all(ctx.in_iteration(o) for o in kernel)
    assert ctx.select(scopes.outside_iteration(ctx))
    assert ctx.breakdown() == expected["breakdown"]
    for m, v in expected["metrics"].items():
        got = harness.load_module(ROOT, "metrics", m).read(ctx)
        assert 0 < got < 100 and math.isclose(got, v, rel_tol=1e-12), (m, got)


def test_instruction_text_is_parsed():
    text = ("%while.22 = (s32[]{:T(128)}, f32[256,4096]{1,0:T(8,128)}) "
            "while((s32[], f32[256,4096]) %tuple.3), condition=%c, body=%b")
    assert trace.parse_instruction(text) == ("%while.22", "(...)", "while",
                                             "")
    text = ('%closed_call.12 = f32[256,4096]{1,0:T(8,128)S(1)} custom-call('
            'f32[256,4096]{1,0} %a), custom_call_target="tpu_custom_call"')
    assert trace.parse_instruction(text) == (
        "%closed_call.12", "f32[256,4096]", "custom-call", "tpu_custom_call")


# -- roofline counts ----------------------------------------------------------


def test_nonsmooth_work_counts_by_hand():
    cfg = json.loads((BENCH / "configs" / "nonsmooth_expander_n256_d4096.json")
                     .read_text())
    module = harness.load_module(ROOT, "problems", "nonsmooth")
    n, M, d, k = 256, 30, 4096, 4
    flops, bytes_ = module.iteration_work(cfg, {"compression": None})
    # the centers, read once: 256 * 30 * 2 * 4096 floats
    assert bytes_ == 4 * n * M * 2 * d == 251_658_240
    assert flops == 3 * n * M * 2 * d + n * M * d + 10 * n * d + 6 * n * d
    assert flops == 236_978_176
    peak = roofline.peaks("TPU v5 lite")
    # memory-bound: 252 MB at 819 GB/s is 0.307 ms; 237 MFLOP at
    # 197 TFLOP/s is 1.2 us
    least = roofline.least_seconds(flops, bytes_, peak)
    assert math.isclose(least, 251_658_240 / 819e9)
    assert math.isclose(least, 3.0727e-4, rel_tol=1e-4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


# -- the command ---------------------------------------------------------


def test_command_exits_nonzero_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "nonsmooth.expander.trace25", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "nonsmooth.expander.trace25", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_reference_data_equals_the_programs():
    """The reference draws the same instance as the program from a seed,
    without taking an array from it."""
    from repro.data.pipeline import metric_learning_pairs
    from repro.experiments.components import nonsmooth_centers
    ns = harness.load_module(ROOT, "problems", "nonsmooth")
    ml = harness.load_module(ROOT, "problems", "metric_learning")
    assert np.array_equal(ns.centers(NONSMOOTH, 9),
                          nonsmooth_centers(16, 5, 256, 9))
    for a, b in zip(ml.pairs(METRIC, 9), metric_learning_pairs(600, 16, 9)):
        assert np.array_equal(a, b)


def test_seed_above_32_bits_gives_same_inputs():
    module = harness.load_module(ROOT, "problems", "nonsmooth")
    seed = 2 ** 33 + 17
    a = module.centers(NONSMOOTH, seed)
    b = module.centers(NONSMOOTH, seed)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, module.centers(NONSMOOTH, 17))
