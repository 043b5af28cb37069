"""Readings that the limits of `bench/limits/<cell>.json` are set from.

    python3 bench/control.py --workload nonsmooth.expander.trace25 \
        --seeds 11 12 13

For each seed, in one process on the chip: one solve through the timed
path (`execute_requests`, as the window drives it), the plain reference
in float32 with matrix products at "highest", and the control: the same
reference computed in the precision below the configuration's (its
`control` entry). The problem kind's hooks (`bench/check.py`) decide
which reference and which numbers, as in the harness: a training cell is
read against `bench/train_ref.py`. Prints one line per seed with the
program's numbers and the control's against the reference, then a
summary line: the largest program reading of each number (the lower end
of its limit) and the smallest control reading (the upper end). The
benchmark's own runs do not run this.

On a circulant graph a DDA cell's seed also runs the reference with each
node's received messages summed one neighbour at a time, and prints its
gaps from the matrix-product reference (`order`): how far a number moves
when only the rounding of the mix changes.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import check, generator, harness  # noqa: E402


def read(cell: harness.Cell, seeds, emit=print) -> dict:
    """Each seed's line, then the summary line, passed to `emit` as JSON
    text; returns the summary."""
    ExperimentSpec, CompileCache, execute_requests = harness._program()
    generator.check_supported(cell.cfg, cell.traffic)
    module = harness.load_module(cell.root, "problems",
                                 cell.cfg["problem"]["kind"])
    kind = check.hooks(module)
    T, every = cell.traffic["T"], cell.traffic["eval_every"]
    circulant = kind.dda and "shifts" in cell.cfg["mixing"]
    lower: dict[str, float] = {}
    upper: dict[str, float] = {}
    order: dict[str, float] = {}
    for seed in seeds:
        t0 = time.perf_counter()
        spec = ExperimentSpec(**generator.solve_request(
            cell.cfg, cell.traffic, seed, 0, kind.spec_problem))
        (result,), _ = execute_requests([spec], [None], CompileCache())
        ref = kind.reference(cell.cfg, cell.traffic, seed, "float32",
                             "highest")
        ctl = kind.reference(cell.cfg, cell.traffic, seed,
                             cell.cfg["control"]["dtype"],
                             cell.cfg["control"]["matmul_precision"])
        prog = kind.readings([result.trace], ref, cell.traffic)
        ctl_r = check.series_gaps(ctl, ref)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in ctl_r.items():
            upper[k] = min(upper.get(k, float("inf")), v)
        line = {"seed": seed, "program": prog, "control": ctl_r}
        if circulant:
            alt = check.reference_trace(module, cell.cfg, cell.traffic, seed,
                                        "float32", "highest",
                                        by_neighbour=True)
            line["order"] = check.series_gaps(alt, ref)
            line["program_vs_order"] = check.readings([result.trace], alt,
                                                      T, every)
            for k, v in line["order"].items():
                order[k] = max(order.get(k, 0.0), v)
        # the first series is F-bar, or a training run's mean loss
        line.update(final_F=result.trace.fvals[-1],
                    ref_final_F=float(next(iter(ref.values()))[-1]),
                    seconds=time.perf_counter() - t0)
        emit(json.dumps(line))
    summary = {"workload": cell.name, "seeds": list(seeds),
               "lower": lower, "upper": upper}
    if circulant:
        summary["order"] = order
    emit(json.dumps(summary))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.configure_jax(cell.cfg)
    read(cell, args.seeds, lambda text: print(text, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
