"""Record the small top-k chip trace that the layer tests read.

    python3 bench/tests/record_topk_trace.py     # on the chip

As `record_trace.py`, with top-k gossip (keep 0.125, error feedback) so
that every named scope of the program shows: two solves of a small
`nonsmooth` instance (n=64, M=8, d=1024, T=50, F every 25, 4-regular
expander) under the profiler, the harness's `bench.solve` span around
each. Writes `bench/tests/data/topk25_small.xplane.pb` and, beside it,
the configuration and what the readers, old and new, read from it then.
"""

from __future__ import annotations

import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator, harness, layers, trace  # noqa: E402
from bench.tests import record_trace  # noqa: E402

OUT = ROOT / "bench" / "tests" / "data" / "topk25_small.xplane.pb"
CFG = record_trace.CFG
TRAFFIC = {**record_trace.TRAFFIC, "name": "topk25",
           "compression": {"kind": "topk", "params": {"keep": 0.125}}}
METRICS = ("device_idle_pct", "eval_pct", "iteration_roofline",
           "topk_sort_pct", "subgrad_pct", "mix_pct", "compress_pct",
           "host_ms_per_solve")


def main() -> int:
    import jax

    device = harness.device_info(1)
    ExperimentSpec, CompileCache, execute_requests = harness._program()
    cache = CompileCache()

    def solve(i):
        spec = ExperimentSpec(**generator.solve_request(CFG, TRAFFIC, 5, i))
        execute_requests([spec], [None], cache)

    solve(0)
    tmp = tempfile.mkdtemp(prefix="bench_record_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in (1, 2):
        with jax.profiler.TraceAnnotation(trace.SOLVE_SPAN):
            solve(i)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    OUT.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, OUT)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(write_expected(device)))
    return 0


def context(device: dict) -> trace.Context:
    """The recorded trace's `Context`, its layers attached."""
    chip_ops, spans = trace.read_xplane(str(OUT), 1)
    cell = harness.Cell(root=ROOT, name="small", chips=1, cfg=CFG,
                        traffic=TRAFFIC, limits={}, end_to_end=[],
                        per_layer=[])
    window = harness.Window(seconds=0.0, solves=2, failed=0,
                            iterations=2 * TRAFFIC["T"], traces=[],
                            errors=[])
    ctx = trace.Context(chip_ops, spans, cell, window, device)
    layers.attach(ctx, str(OUT))
    return ctx


def write_expected(device: dict) -> dict:
    """What the readers read from the recorded trace, written beside it."""
    ctx = context(device)
    expected = {"cfg": CFG, "traffic": TRAFFIC, "device": device,
                "iterations": ctx.window.iterations, "busy_s": ctx.busy_s,
                "window_s": ctx.window_s, "breakdown": layers.breakdown(ctx),
                "coverage": layers.coverage(ctx),
                "metrics": {m: harness.load_module(ROOT, "metrics", m)
                            .read(ctx) for m in METRICS}}
    OUT.with_suffix(".json").write_text(json.dumps(expected, indent=1))
    return expected


if __name__ == "__main__":
    sys.exit(main())
