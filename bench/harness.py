"""Runs one cell of the benchmark: set-up, a measured window of solves,
and the check of what the window produced.

Everything that belongs to one configuration, traffic mix, per-layer
metric, device kind or cell sits in a file of its own, found by the name
that `BENCHMARK.json` gives it:

  bench/configs/<config>.json        sizes, graph, stepsize, precision
  bench/traffic/<traffic>.json       the solve mix (bench/generator.py)
  bench/problems/<problem kind>.py   plain reference and work counts, or
                                     the plain model of a training cell
                                     with `bench/train_ref.py`'s hooks
  bench/metrics/<metric>.py          `read(ctx)` of one per-layer metric
  bench/peaks/<device kind>.json     the chip's published peaks
  bench/limits/<cell>.json           the limit of each number compared

The window drives the serving layer's execution entry,
`repro.serve.execute_requests`, closed loop: one client, one solve after
another, through one `CompileCache` warmed in set-up and held for the
whole window, as the experiment server holds it. A solve of a `launch`
configuration is one consensus training run of T steps, and the trace it
returns holds its losses.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: JAX's persistent compile cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell `name` of `root/BENCHMARK.json` with its files."""
    bench = _load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = _load_json(root / config["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in reported]
    limits = _load_json(root / "bench" / "limits" / f"{name}.json")
    return Cell(root=root, name=name, chips=int(w["chips"]), cfg=cfg,
                traffic=traffic, limits=limits, end_to_end=end_to_end,
                per_layer=per_layer)


def load_module(root: pathlib.Path, group: str, name: str):
    """`root/bench/<group>/<name>.py` as a module, found by its path."""
    path = root / "bench" / group / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.{group}.{name}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def device_info(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; raises `NoChip` unless
    they are accelerators and at least `chips` of them."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] not in ("tpu", "gpu"):
        raise NoChip(f"no accelerator: JAX runs on {info['platform']}")
    if info["count"] < chips:
        raise NoChip(f"{chips} chips needed, JAX found {info['count']}")
    return info


def configure_jax(cfg: dict) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # no size limit, whatever the environment sets: a limited cache keeps
    # an access-time file beside each entry, and in a directory holding
    # one entry without it every write fails, so that each run compiles
    jax.config.update("jax_compilation_cache_max_size", -1)
    # every program, however quick to compile, comes from the cache after
    # the first run, so that set-up stays the same from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])


def _program():
    """The system under test's entry points."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.experiments import ExperimentSpec
    from repro.serve import CompileCache, execute_requests
    return ExperimentSpec, CompileCache, execute_requests


def _peak_bytes() -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


@dataclasses.dataclass
class Window:
    """What the measured window did, on the host's clock."""

    seconds: float
    solves: int
    failed: int
    iterations: int
    traces: list
    errors: list
    #: each solve's wall time, in seconds
    solve_walls: list = dataclasses.field(default_factory=list)


def run_window(cell: Cell, seed: int, seconds: float, ExperimentSpec,
               cache, execute_requests, annotate, spec_problem) -> Window:
    """Solves back to back until `seconds` have passed; the last solve
    started in time runs to its end and the window closes with it."""
    from bench import generator, trace

    traces, errors, walls = [], [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    index = 0
    while time.perf_counter() < deadline:
        index += 1
        spec = ExperimentSpec(**generator.solve_request(
            cell.cfg, cell.traffic, seed, index, spec_problem))
        ts = time.perf_counter()
        try:
            with annotate(trace.SOLVE_SPAN):
                (result,), _ = execute_requests([spec], [None], cache)
            traces.append(result.trace)
        except Exception as e:  # a failed solve is counted, not fatal
            errors.append(f"{type(e).__name__}: {e}")
        walls.append(time.perf_counter() - ts)
    t1 = time.perf_counter()
    return Window(seconds=t1 - t0, solves=index, failed=len(errors),
                  iterations=cell.traffic["T"] * len(traces),
                  traces=traces, errors=errors, solve_walls=walls)


def window_summary(win: Window) -> str:
    """One line for standard error on how the window's solves took their
    time, so that a slow one shows with its place in the window."""
    w = sorted(win.solve_walls) or [0.0]
    slowest = max(range(len(win.solve_walls)),
                  key=win.solve_walls.__getitem__, default=-1)
    return (f"window {win.seconds!r} s, {win.solves} solves, solve wall "
            f"min {w[0]!r} median {w[len(w) // 2]!r} max {w[-1]!r} s "
            f"(solve {slowest + 1})")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict) -> dict:
    """One run of `cell`; returns the result line's object."""
    import jax

    from bench import check, generator

    generator.check_supported(cell.cfg, cell.traffic)
    kind = check.hooks(load_module(cell.root, "problems",
                                   cell.cfg["problem"]["kind"]))
    ExperimentSpec, CompileCache, execute_requests = _program()
    cache = CompileCache()
    # set-up: the problem's data, the simulator, its compiled programs
    # (from the persistent cache after a cell's first run), and one solve
    warm = ExperimentSpec(**generator.solve_request(
        cell.cfg, cell.traffic, seed, 0, kind.spec_problem))
    execute_requests([warm], [None], cache)
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        import contextlib

        def annotate(name):
            return contextlib.nullcontext()
    try:
        win = run_window(cell, seed, seconds, ExperimentSpec, cache,
                         execute_requests, annotate, kind.spec_problem)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = _peak_bytes()
    del cache, warm
    gc.collect()

    result: dict = {"correct": False, "attempted": win.solves,
                    "failed": win.failed}
    if trace:
        try:
            metrics, dev_extra, breakdown = _per_layer(cell, win, trace_dir,
                                                       device)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = metrics
        result["breakdown"] = breakdown
    else:
        dev_extra = {}
        result["metrics"] = _end_to_end(cell, win, setup_s)
    result["device"] = {**device, "memory_peak_bytes": memory_peak,
                        **dev_extra}

    ref = kind.reference(cell.cfg, cell.traffic, seed, "float32", "highest")
    values = kind.readings(win.traces, ref, cell.traffic)
    ok, checks = check.judge(values, cell.limits, kind.numbers)
    result["correct"] = bool(ok and win.failed == 0 and win.traces)
    result["errors"] = win.errors[:3]
    result["window"] = window_summary(win)
    result["checks"] = checks  # last: the numbers compared end the line
    return result


def _end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    values = {"iters_per_s": win.iterations / win.seconds,
              "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def _per_layer(cell: Cell, win: Window, trace_dir: str, device: dict):
    from bench import trace as trace_mod

    ctx = trace_mod.context(trace_dir, cell=cell, window=win, device=device)
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(cell.root, "metrics", m["name"])
        value = reader.read(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": ctx.busy_s, "window_s": ctx.window_s}
    return metrics, extra, ctx.breakdown()


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        device = device_info(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    configure_jax(cell.cfg)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, device)
    print(result["window"], file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
