"""Compressed-gossip benchmark: bytes on wire vs time to accuracy.

Sweeps the compressor axis (`none` / `topk` / `randk` / `int8`) over the
SAME bandwidth-limited experiment on both execution modes:

  * dense -- `DDASimulator` with the fused compress-mix pass on a
    k-regular expander; the simulated time axis charges the effective
    tradeoff r*c (c = the compressor's wire ratio).
  * netsim -- the event-driven cluster on a homogeneous scenario whose
    link serialization time dominates compute (large r), with sender-side
    compression scaling `Network.wire_bytes`.

Before ANY timing it runs the equivalence gate: the object and
vectorized netsim engines must be BIT-identical under every compressor in
the sweep. (The fused compress-mix pass is held to the dense-matmul
oracle by tests/test_compress.py.)

Acceptance (enforced in both modes): on the bandwidth-limited netsim
scenario at least one compressed cell must reach the 2% accuracy gap
FASTER (event clock) than the uncompressed baseline, and the paper's
`tradeoff.time_to_accuracy` evaluated at r*c must predict the measured
dense frontier ordering across compressors.

Results land in BENCH_compress.json (schema in benchmarks/README.md); the
CI tier-1 job runs `--smoke` on every push and uploads the JSON.
"""

from __future__ import annotations

import argparse
import math
import platform
import statistics
import time

import numpy as np

from repro.core import tradeoff
from repro.experiments import ExperimentSpec, run as run_spec
from repro.obs import sample_quantiles, write_json_artifact

#: eps the predicted frontier is quoted at (matches runner.PREDICT_EPS)
PREDICT_EPS = 0.1

#: the compressor axis: one uncompressed baseline + the three wire formats
COMPRESSION_AXIS = [
    ("none", None),
    ("topk", {"kind": "topk", "params": {"keep": 0.25}}),
    ("randk", {"kind": "randk", "params": {"keep": 0.25}}),
    ("int8", {"kind": "int8", "params": {}}),
]


def cell_spec(n: int, d: int, T: int, r: float, k: int, seed: int,
              eval_every: int, backend: dict, compression,
              eps_frac: float) -> ExperimentSpec:
    """One bandwidth-limited cell: quadratic consensus on a k-regular
    expander, communicate every iteration (maximum wire pressure)."""
    return ExperimentSpec(
        name="bench_compress",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": n, "d": d, "seed": seed}},
        topology={"kind": "expander", "params": {"k": k, "seed": seed}},
        schedule={"kind": "every"},
        backends=[backend],
        stepsize={"kind": "sqrt", "params": {"A": 0.5}},
        compression=compression,
        T=T, eval_every=eval_every, seed=seed, r=r, eps_frac=eps_frac)


# ---------------------------------------------------------------------------
# equivalence gate
# ---------------------------------------------------------------------------


def check_netsim_engine_identity(n: int, d: int, T: int, r: float, k: int,
                                 seed: int, eval_every: int) -> dict:
    """Object vs vectorized engines, bit-identical traces under every
    compressor on the sweep axis."""
    checked = []
    ok = True
    for label, comp in COMPRESSION_AXIS:
        runs = {}
        for engine in ("object", "vectorized"):
            res = run_spec(cell_spec(
                n, d, T, r, k, seed, eval_every,
                {"kind": "netsim", "params": {"scenario": "homogeneous",
                                              "engine": engine}},
                comp, eps_frac=None))
            runs[engine] = res.trace
        same = (runs["object"].fvals == runs["vectorized"].fvals
                and runs["object"].sim_time == runs["vectorized"].sim_time)
        checked.append({"compression": label, "bit_identical": bool(same)})
        ok = ok and same
    return {"n": n, "d": d, "T": T, "cells": checked, "ok": bool(ok)}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------


def bench_cell(backend: dict, label: str, comp, n: int, d: int, T: int,
               r: float, k: int, seed: int, eval_every: int,
               eps_frac: float, repeats: int) -> dict:
    """Time one (backend, compressor) cell: a cold run, then `repeats`
    warm repeats (median wall), reporting the tradeoff-relevant outputs:
    bytes on wire, time-to-accuracy on the simulated clock, and the
    effective-r predictions."""
    spec = cell_spec(n, d, T, r, k, seed, eval_every, backend, comp,
                     eps_frac)
    res = run_spec(spec)
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = run_spec(spec)
        walls.append(time.perf_counter() - t0)
    wall = statistics.median(walls)
    m = res.metrics
    tta = res.time_to_target
    return {"backend": backend["kind"], "compression": label,
            "n": n, "d": d, "T": T, "r": r,
            "wire_ratio": (1.0 if m.compression is None
                           else m.compression["wire_ratio"]),
            "bytes_on_wire": m.bytes_on_wire,
            "bytes_saved": (0.0 if m.compression is None
                            else m.compression["bytes_saved"]),
            "time_to_target": (None if tta is None or math.isinf(tta)
                               else tta),
            "final_f": float(res.trace.fvals[-1]),
            "wall_s": round(wall, 4),
            "wall_samples_s": [round(w, 6) for w in walls],
            "wall_quantiles": sample_quantiles(walls, "host"),
            "metrics": m.to_dict(),
            "predictions": res.predictions}


def frontier_check(cells: list[dict], n: int, k: int, r: float,
                   lam2: float) -> dict:
    """The paper's design rule at the effective tradeoff: the predicted
    tau(eps; r*c) ordering across compressors must match the measured
    time-to-accuracy ordering on the bandwidth-limited cells.  Cells
    that never reach the gap within T are excluded (and reported)."""
    measured = [(c["compression"], c["time_to_target"])
                for c in cells if c["time_to_target"] is not None]
    predicted = [(c["compression"],
                  tradeoff.time_to_accuracy(PREDICT_EPS, n, k, r, lam2,
                                            c=c["wire_ratio"]))
                 for c in cells if c["time_to_target"] is not None]
    m_order = [lab for lab, _ in sorted(measured, key=lambda kv: kv[1])]
    p_order = [lab for lab, _ in sorted(predicted, key=lambda kv: kv[1])]
    excluded = [c["compression"] for c in cells
                if c["time_to_target"] is None]
    return {"measured_order": m_order, "predicted_order": p_order,
            "predicted_tau": {lab: t for lab, t in predicted},
            "measured_tta": {lab: t for lab, t in measured},
            "excluded": excluded,
            "ok": bool(m_order == p_order and len(m_order) >= 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=32, help="cluster size")
    ap.add_argument("--d", type=int, default=256, help="dimension")
    ap.add_argument("--k", type=int, default=4, help="expander degree")
    ap.add_argument("--T", type=int, default=600, help="iterations (dense)")
    ap.add_argument("--r", type=float, default=0.5,
                    help="tradeoff: large = bandwidth-limited (k*r >> 1/n)")
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eps-frac", type=float, default=0.02,
                    help="accuracy gap the time-to-target clock stops at")
    ap.add_argument("--netsim-n", type=int, default=16)
    ap.add_argument("--netsim-d", type=int, default=64)
    ap.add_argument("--netsim-T", type=int, default=600)
    ap.add_argument("--repeats", type=int, default=2,
                    help="warm timing repeats per cell (median; 1 in "
                         "--smoke)")
    ap.add_argument("--out", default="BENCH_compress.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes, single repeat: CI acceptance mode "
                         "(equivalence + tradeoff gates still enforced)")
    args = ap.parse_args(argv)

    n, d, T = args.n, args.d, args.T
    nn, nd, nT = args.netsim_n, args.netsim_d, args.netsim_T
    repeats = args.repeats
    if args.smoke:
        n, d, T = min(n, 16), min(d, 128), min(T, 300)
        nn, nd, nT = min(nn, 8), min(nd, 32), min(nT, 300)
        repeats = 1

    # correctness gate before any timing
    gate = check_netsim_engine_identity(min(nn, 8), min(nd, 32), T=60,
                                        r=args.r, k=args.k, seed=args.seed,
                                        eval_every=args.eval_every)
    print(f"[equivalence] netsim object vs vectorized under compression: "
          f"{'OK' if gate['ok'] else 'FAIL'}")
    if not gate["ok"]:
        return 1

    results = []
    print("backend,compression,wire_ratio,bytes_on_wire,time_to_target")
    for backend, (bn, bd, bT) in (
            ({"kind": "dense", "params": {}}, (n, d, T)),
            ({"kind": "netsim", "params": {"scenario": "homogeneous",
                                           "engine": "auto"}},
             (nn, nd, nT))):
        for label, comp in COMPRESSION_AXIS:
            cell = bench_cell(backend, label, comp, bn, bd, bT, args.r,
                              args.k, args.seed, args.eval_every,
                              args.eps_frac, repeats)
            results.append(cell)
            print(f"{cell['backend']},{label},{cell['wire_ratio']:.4g},"
                  f"{cell['bytes_on_wire']:.4g},{cell['time_to_target']}")

    dense_cells = [c for c in results if c["backend"] == "dense"]
    net_cells = [c for c in results if c["backend"] == "netsim"]

    # acceptance: a compressed netsim cell beats the uncompressed baseline
    # to the eps_frac gap on the event clock
    base = next(c for c in net_cells if c["compression"] == "none")
    beat = [c["compression"] for c in net_cells
            if c["compression"] != "none"
            and c["time_to_target"] is not None
            and base["time_to_target"] is not None
            and c["time_to_target"] < base["time_to_target"]]
    bandwidth_win = {
        "baseline_tta": base["time_to_target"],
        "compressed_faster": beat,
        "ok": bool(beat),
    }
    print(f"[acceptance] compressed beats uncompressed to "
          f"{args.eps_frac:.0%} gap on netsim: {beat or 'NONE'}")

    # acceptance: tau(r*c) predicts the measured frontier ordering on the
    # bandwidth-limited netsim cells (dense cells are reported alongside;
    # at small d sparsifier bias can locally reorder them)
    from repro.experiments.components import topologies
    net_lam2 = topologies.build("expander", n=nn, k=args.k,
                                seed=args.seed).lambda2()
    frontier = frontier_check(net_cells, nn, args.k, args.r, net_lam2)
    lam2 = topologies.build("expander", n=n, k=args.k,
                            seed=args.seed).lambda2()
    frontier["dense"] = frontier_check(dense_cells, n, args.k, args.r,
                                       lam2)
    print(f"[acceptance] tau(r*c) frontier ordering "
          f"{frontier['predicted_order']} vs measured "
          f"{frontier['measured_order']}: "
          f"{'OK' if frontier['ok'] else 'FAIL'}")

    report = {
        "benchmark": "compress",
        "mode": "smoke" if args.smoke else "full",
        "config": {"n": n, "d": d, "T": T, "k": args.k, "r": args.r,
                   "netsim_n": nn, "netsim_d": nd, "netsim_T": nT,
                   "eval_every": args.eval_every, "seed": args.seed,
                   "eps_frac": args.eps_frac, "schedule": "every",
                   "repeats": repeats,
                   "compression_axis": [label for label, _ in
                                        COMPRESSION_AXIS]},
        "host": {"platform": platform.platform(),
                 "python": platform.python_version(),
                 "numpy": np.__version__},
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "equivalence": {"netsim_engines": gate, "ok": bool(gate["ok"])},
        "results": results,
        "bandwidth_win": bandwidth_win,
        "frontier": frontier,
    }
    write_json_artifact(args.out, report)
    print(f"[bench_compress] wrote {args.out}")

    if not (bandwidth_win["ok"] and frontier["ok"]):
        print("[bench_compress] FAIL: tradeoff acceptance gates")
        return 1
    print("[bench_compress] OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
