"""Record the small chip trace that the trace-reduction test reads.

    python3 bench/tests/record_trace.py     # on the chip

Runs two solves of a small `nonsmooth` instance (n=64, M=8, d=1024, T=50,
F every 25, 4-regular expander) through the timed path under the
profiler, with the harness's `bench.solve` span around each, and writes
`bench/tests/data/trace25_small.xplane.pb` and, beside it, the
configuration, the iteration count and what the per-layer readers read
from it then.
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

from bench import generator, harness, trace  # noqa: E402

OUT = ROOT / "bench" / "tests" / "data" / "trace25_small.xplane.pb"
CFG = {
    "name": "nonsmooth_small",
    "problem": {"kind": "nonsmooth", "params": {"n": 64, "M": 8, "d": 1024}},
    "topology": {"kind": "expander", "params": {"k": 4, "seed": 0}},
    "mixing": {"shifts": [1, 63, 28, 36], "self_weight": 0.2,
               "edge_weight": 0.2},
    "stepsize": {"kind": "sqrt", "params": {"A": 0.004, "q": 0.5}},
    "r": 0.01, "backend": {"kind": "dense", "params": {}},
    "precision": "float32", "matmul_precision": None,
}
TRAFFIC = {"name": "small25", "loop": "closed", "clients": 1,
           "schedule": {"kind": "every"}, "T": 50, "eval_every": 25,
           "compression": None}
METRICS = ("device_idle_pct", "eval_pct", "iteration_roofline")


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


def write_expected(device: dict) -> dict:
    """What the readers read from the recorded trace, written beside it."""
    chip_ops, spans = trace.read_xplane(str(OUT), 1)
    cell = harness.Cell(root=ROOT, name="small", chips=1, cfg=CFG,
                        traffic=TRAFFIC, limits={}, end_to_end=[],
                        per_layer=[])
    window = harness.Window(seconds=0.0, solves=2, failed=0,
                            iterations=2 * TRAFFIC["T"], traces=[],
                            errors=[])
    ctx = trace.Context(chip_ops, spans, cell, window, device)
    expected = {"cfg": CFG, "traffic": TRAFFIC, "device": device,
                "iterations": window.iterations, "busy_s": ctx.busy_s,
                "window_s": ctx.window_s, "breakdown": ctx.breakdown(),
                "metrics": {m: harness.load_module(ROOT, "metrics", m)
                            .read(ctx) for m in METRICS}}
    OUT.with_suffix(".json").write_text(json.dumps(expected, indent=1))
    return expected


if __name__ == "__main__":
    sys.exit(main())
