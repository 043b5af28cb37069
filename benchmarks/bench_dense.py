"""Dense fast-path benchmark: fused+scanned DDASimulator vs the seed path.

Times the device-resident dense fast path -- sparse gossip mix
(`kernels.ops.gossip_gather_mix`: neighbor-index gather + fused weighted
accumulation, O(nkd)) driven by the fully-scanned segment loop (one
compiled program per run) -- against the SEED configuration it replaced:
the dense `P @ z` matmul mix (O(n^2 d)) under the host-side per-segment
dispatch loop (`DDASimulator.run(loop="segment", mix="dense")`). Also
times `run_sweep(parallel="vmap")` (one compile + one batched dispatch for
a seed grid) against the serial executor (a fresh trace+compile per cell).

Before ANY timing it runs the equivalence gates: the fused path's fvals
must match the seed path's on the same seeded run to <= --tol relative
(the gather+FMA mix reorders float accumulation vs the matmul, so bitwise
equality is not expected), and the vmapped sweep must match the serial
sweep cell-for-cell. A fast-but-wrong path can never post a number.

Results land in BENCH_dense.json (schema in benchmarks/README.md); the CI
tier-1 job runs `--smoke` on every push and uploads the JSON. Full mode
exits nonzero unless both speedups reach --min-speedup (default 3x) at the
acceptance shape n=256, k=4, d=4096.
"""

from __future__ import annotations

import argparse
import platform
import statistics
import time

import numpy as np

from repro.core.dda import DDASimulator, stepsize_sqrt
from repro.core.schedules import EveryIteration
from repro.experiments import ExperimentSpec, run as run_spec, run_sweep
from repro.experiments.components import problems, topologies
from repro.obs import (RunMetrics, Tracer, sample_quantiles,
                       write_json_artifact)

SEED_BACKEND = {"kind": "dense", "params": {"mix": "dense",
                                            "loop": "segment"}}
FUSED_BACKEND = {"kind": "dense", "params": {}}


def cell_spec(n: int, d: int, T: int, r: float, k: int, seed: int,
              eval_every: int, backend: dict) -> ExperimentSpec:
    """One dense cell: quadratic consensus on a k-regular expander,
    communicate every iteration (maximum mixing pressure)."""
    return ExperimentSpec(
        name="bench_dense",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": n, "d": d, "seed": seed}},
        topology={"kind": "expander", "params": {"k": k, "seed": seed}},
        schedule={"kind": "every"},
        backends=[backend],
        stepsize={"kind": "sqrt", "params": {"A": 0.05}},
        T=T, eval_every=eval_every, seed=seed, r=r)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)))


def check_equivalence(n: int, d: int, T: int, r: float, k: int, seed: int,
                      eval_every: int, tol: float) -> dict:
    """Seed-vs-fused fvals on one seeded run, to tol relative."""
    seed_res = run_spec(cell_spec(n, d, T, r, k, seed, eval_every,
                                  SEED_BACKEND))
    fused_res = run_spec(cell_spec(n, d, T, r, k, seed, eval_every,
                                   FUSED_BACKEND))
    assert fused_res.extras["mix_mode"] == "sparse", (
        "acceptance shape must engage the sparse fast path, got "
        f"{fused_res.extras['mix_mode']}")
    rel = _rel(seed_res.trace.fvals, fused_res.trace.fvals)
    same_axes = (seed_res.trace.iters == fused_res.trace.iters
                 and seed_res.trace.sim_time == fused_res.trace.sim_time
                 and seed_res.trace.comms == fused_res.trace.comms)
    return {"n": n, "d": d, "T": T, "fvals_rel": rel, "tol": tol,
            "axes_identical": bool(same_axes),
            "ok": bool(same_axes and rel <= tol)}


def bench_path(n: int, d: int, T: int, r: float, k: int, seed: int,
               eval_every: int, mix: str, loop: str, label: str,
               repeats: int) -> dict:
    """Steady-state wall of one path: a cold run pays trace+compile (kept
    as `cold_wall_s`), then the reported `wall_s` is the median of
    `repeats` warm runs on the same simulator -- the throughput a sweep or
    long run actually sees, robust to this-box load spikes (the matmul
    path's multithreaded BLAS timing is noisy)."""
    import jax
    import jax.numpy as jnp

    prob = problems.build("quadratic_consensus", n=n, d=d, seed=seed)
    graph = topologies.build("expander", n=n, k=k, seed=seed)
    sim = DDASimulator(prob.subgrad_stack, jax.jit(prob.objective), graph,
                       EveryIteration(), a_fn=stepsize_sqrt(0.05), r=r,
                       mix=mix)
    x0 = jnp.zeros((n, d))
    t0 = time.perf_counter()
    trace = sim.run(x0, T, eval_every=eval_every, seed=seed, loop=loop)
    cold = time.perf_counter() - t0
    compile_s = sim.last_timings["compile_s"]  # cold run pays the compile
    walls = []
    for _ in range(repeats):
        sim.tracer = Tracer()  # the phases of each warm run on its own
        t0 = time.perf_counter()
        trace = sim.run(x0, T, eval_every=eval_every, seed=seed, loop=loop)
        walls.append(time.perf_counter() - t0)
    wall = statistics.median(walls)
    # the last warm run's spans: `wait` is the blocked wait for results
    metrics = RunMetrics(compile_s=compile_s, execute_s=wall,
                         phases=sim.tracer.phase_totals())
    sim.tracer = None
    return {"path": label, "n": n, "d": d, "T": T, "k": k,
            "wall_s": round(wall, 4),
            "cold_wall_s": round(cold, 4),
            "iters_per_s": round(T / wall, 1),
            # the FULL warm-run sample array + its quantiles: regression
            # tooling wants the distribution, not just the median
            "wall_samples_s": [round(w, 6) for w in walls],
            "wall_quantiles": sample_quantiles(walls, "host"),
            "metrics": metrics.to_dict(),
            "final_f": float(trace.fvals[-1]),
            "mix_mode": sim.mix_mode}


def bench_sweep(n: int, d: int, T: int, r: float, k: int, seed: int,
                eval_every: int, cells: int, tol: float) -> dict:
    """Serial vs vmapped run_sweep on a seed axis, equivalence first."""
    spec = cell_spec(n, d, T, r, k, seed, eval_every, FUSED_BACKEND)
    seeds = list(range(cells))
    t0 = time.perf_counter()
    serial = run_sweep(spec, "seed", seeds)
    serial_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    vmapped = run_sweep(spec, "seed", seeds, parallel="vmap")
    vmap_wall = time.perf_counter() - t0
    assert all("vmap_lanes" in res.extras for res in vmapped), (
        "vmap executor silently fell back to serial -- the cells must be "
        "shape-compatible")
    rel = max(_rel(a.trace.fvals, b.trace.fvals)
              for a, b in zip(serial, vmapped))
    return {"cells": cells, "n": n, "d": d, "T": T,
            "serial_wall_s": round(serial_wall, 4),
            "vmap_wall_s": round(vmap_wall, 4),
            "speedup": round(serial_wall / vmap_wall, 2),
            # one lane's metrics block: the amortized compile/execute
            # split every vmapped cell reports through repro.run()
            "vmap_lane_metrics": vmapped[0].metrics.to_dict(),
            "fvals_rel": rel, "tol": tol, "ok": bool(rel <= tol)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=256, help="cluster size")
    ap.add_argument("--d", type=int, default=4096, help="dimension")
    ap.add_argument("--k", type=int, default=4, help="expander degree")
    ap.add_argument("--T", type=int, default=120, help="iterations")
    ap.add_argument("--r", type=float, default=0.01)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tol", type=float, default=1e-5,
                    help="relative fvals tolerance for the equivalence gates")
    ap.add_argument("--min-speedup", type=float, default=3.0,
                    help="required fused/seed AND vmap/serial speedup "
                         "(full mode)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="warm timing repeats per path (median; 1 in "
                         "--smoke)")
    ap.add_argument("--sweep-cells", type=int, default=8)
    ap.add_argument("--sweep-n", type=int, default=64)
    ap.add_argument("--sweep-d", type=int, default=512)
    ap.add_argument("--sweep-T", type=int, default=120)
    ap.add_argument("--out", default="BENCH_dense.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes, single repeat, no speedup gate: "
                         "CI acceptance mode (equivalence still enforced)")
    args = ap.parse_args(argv)

    n, d, T = args.n, args.d, args.T
    sweep_cells, sweep_n, sweep_d, sweep_T = (args.sweep_cells, args.sweep_n,
                                              args.sweep_d, args.sweep_T)
    repeats = args.repeats
    if args.smoke:
        n, d, T = min(n, 32), min(d, 512), min(T, 60)
        sweep_cells, sweep_n, sweep_d, sweep_T = 4, 16, 64, 60
        repeats = 1

    # correctness gates before any timing
    equiv = check_equivalence(min(n, 64), min(d, 256), T=60, r=args.r,
                              k=args.k, seed=args.seed,
                              eval_every=args.eval_every, tol=args.tol)
    print(f"[equivalence] fused vs seed fvals rel={equiv['fvals_rel']:.2e} "
          f"(tol {args.tol:g}): {'OK' if equiv['ok'] else 'FAIL'}")
    if not equiv["ok"]:
        return 1
    sweep = bench_sweep(sweep_n, sweep_d, sweep_T, args.r, args.k,
                        args.seed, args.eval_every, sweep_cells, args.tol)
    print(f"[equivalence] vmap vs serial sweep rel={sweep['fvals_rel']:.2e}"
          f": {'OK' if sweep['ok'] else 'FAIL'}")
    if not sweep["ok"]:
        return 1

    results = []
    print("path,n,d,T,wall_s,iters_per_s")
    for mix, loop, label in (("dense", "segment", "seed_matmul_segment"),
                             ("auto", "scan", "fused_scan")):
        cell = bench_path(n, d, T, args.r, args.k, args.seed,
                          args.eval_every, mix, loop, label, repeats)
        results.append(cell)
        print(f"{label},{n},{d},{T},{cell['wall_s']},{cell['iters_per_s']}")

    run_speedup = round(results[0]["wall_s"] / results[1]["wall_s"], 2)
    print(f"[speedup] fused+scanned vs seed: {run_speedup:.1f}x")
    print(f"[speedup] vmapped vs serial sweep ({sweep['cells']} cells): "
          f"{sweep['speedup']:.1f}x")

    report = {
        "benchmark": "dense_fast_path",
        "mode": "smoke" if args.smoke else "full",
        "config": {"n": n, "d": d, "T": T, "k": args.k, "r": args.r,
                   "eval_every": args.eval_every, "seed": args.seed,
                   "schedule": "every", "repeats": repeats,
                   "tol": args.tol},
        "host": {"platform": platform.platform(),
                 "python": platform.python_version(),
                 "numpy": np.__version__},
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "equivalence": equiv,
        "results": results,
        "sweep": sweep,
        "speedups": {"run": run_speedup, "sweep": sweep["speedup"]},
    }
    write_json_artifact(args.out, report)
    print(f"[bench_dense] wrote {args.out}")

    if not args.smoke:
        fails = []
        if run_speedup < args.min_speedup:
            fails.append(f"fused/seed {run_speedup:.1f}x")
        if sweep["speedup"] < args.min_speedup:
            fails.append(f"vmap/serial {sweep['speedup']:.1f}x")
        if fails:
            print(f"[bench_dense] FAIL: {', '.join(fails)} < "
                  f"{args.min_speedup:g}x")
            return 1
        print(f"[bench_dense] OK: run {run_speedup:.1f}x, sweep "
              f"{sweep['speedup']:.1f}x >= {args.min_speedup:g}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
