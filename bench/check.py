"""What decides `correct`: the timed solves' traces against the plain
reference of `bench/dda_ref.py`.

Every solve of a run is the same request, so the reference runs once and
every solve that the window completed is compared with it. The numbers
compared, each against a limit of its own from `bench/limits/<cell>.json`:

  fbar_rel_gap          F-bar, the mean over nodes of F at each node's
                        running average, at every trace point
  fxbar_rel_gap         F at the average of the running averages
  disagreement_rel_gap  max_i ||z_i - mean z||, the network's disagreement
  trace_layout_mismatch solves whose trace points are not at eval_every,
                        2 eval_every, ..., T (an exact check, limit 0)

A gap is max |program - reference| / |reference| over trace points and
solves; a value that is not finite reads as infinite. A cell compares
the numbers its limits file names.

Those are the DDA's numbers. A problem module (`bench/problems/<kind>.py`)
may bring its own: `spec_problem`, `reference_trace`, `readings` and
`NUMBERS` (`bench/train_ref.py` has them for training); `hooks` decides,
for the harness and `bench/control.py` alike, which ones a cell uses.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import numpy as np

from bench import dda_ref, generator

NUMBERS = ("fbar_rel_gap", "fxbar_rel_gap", "disagreement_rel_gap",
           "trace_layout_mismatch")

#: the program's trace field for each reference series
_FIELDS = {"fbar": "fvals", "fxbar": "fvals_consensus",
           "disagreement": "disagreement"}


@dataclasses.dataclass(frozen=True)
class Hooks:
    """What a cell's problem kind supplies to the harness.

    spec_problem: (cfg, seed) -> the `problem` of the solve's spec
    reference:    (cfg, traffic, seed, dtype, matmul_precision) -> the
                  reference's series, each a float64 array over trace points
    readings:     (traces, reference, traffic) -> {number: value}
    numbers:      the names of the numbers a limits file may hold
    dda:          the DDA's own hooks, those of a module that brings none
    """

    spec_problem: Callable
    reference: Callable
    readings: Callable
    numbers: tuple
    dda: bool


def hooks(module) -> Hooks:
    """The hooks of `module`, a problem module; where it brings none of
    its own, the DDA's of this file."""
    own = [name for name in ("spec_problem", "reference_trace", "readings",
                             "NUMBERS") if hasattr(module, name)]
    if not own:
        return Hooks(spec_problem=generator.seeded_problem,
                     reference=functools.partial(reference_trace, module),
                     readings=dda_readings, numbers=NUMBERS, dda=True)
    if len(own) < 4:
        raise AttributeError(f"{module.__name__} brings {own}: a problem "
                             f"module brings all four hooks or none")
    return Hooks(spec_problem=module.spec_problem,
                 reference=functools.partial(module.reference_trace, module),
                 readings=module.readings, numbers=tuple(module.NUMBERS),
                 dda=False)


def dda_readings(traces, reference: dict, traffic: dict) -> dict:
    return readings(traces, reference, traffic["T"], traffic["eval_every"])


def series_gaps(series: dict, reference: dict) -> dict:
    """`<series>_rel_gap` of each series against the reference's."""
    return {f"{k}_rel_gap": rel_gap(series[k], reference[k])
            for k in reference}


def keep_count(d: int, keep: float) -> int:
    """Entries a top-k message keeps at fraction `keep` (at least one)."""
    return max(1, min(d, int(d * keep)))


def mixing(cfg: dict) -> np.ndarray:
    n = cfg["problem"]["params"]["n"]
    m = cfg["mixing"]
    if m.get("complete"):
        return dda_ref.complete_matrix(n)
    return dda_ref.mixing_matrix(n, m["shifts"], m["self_weight"],
                                 m["edge_weight"])


def reference_trace(module, cfg: dict, traffic: dict, seed: int,
                    dtype: str, matmul_precision: str | None,
                    by_neighbour: bool = False) -> dict[str, np.ndarray]:
    """The reference's trace of the solve that `seed` makes, computed in
    `dtype` with matrix products at `matmul_precision`; `module` is the
    configuration's problem module (`bench/problems/<kind>.py`).
    `by_neighbour` sums each node's received messages one neighbour at a
    time instead of by a matrix product (a circulant graph only)."""
    import jax
    import jax.numpy as jnp

    problem = module.reference_problem(cfg, seed, jnp.dtype(dtype))
    comp = traffic.get("compression")
    keep = None
    if comp is not None:
        if comp["kind"] != "topk":
            raise ValueError(f"the reference has no {comp['kind']!r} "
                             f"compressor")
        keep = keep_count(problem.dim, comp["params"]["keep"])
    step = cfg["stepsize"]["params"]
    with jax.default_matmul_precision(matmul_precision):
        return dda_ref.run(problem, mixing(cfg), step["A"],
                           step.get("q", 0.5), traffic["T"],
                           traffic["eval_every"], dtype=jnp.dtype(dtype),
                           topk_keep=keep,
                           shifts=cfg["mixing"]["shifts"] if by_neighbour
                           else None)


def rel_gap(program, reference) -> float:
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    if p.shape != r.shape:
        return math.inf
    gap = np.abs(p - r) / np.maximum(np.abs(r), np.finfo(np.float64).tiny)
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else math.inf


def readings(traces, reference: dict, T: int, eval_every: int) -> dict:
    """The numbers compared, over the traces of every completed solve."""
    out = {f"{key}_rel_gap": 0.0 for key in _FIELDS}
    layout = list(range(eval_every, T + 1, eval_every))
    mismatch = 0
    for tr in traces:
        if list(tr.iters) != layout:
            mismatch += 1
        for key, field in _FIELDS.items():
            name = f"{key}_rel_gap"
            out[name] = max(out[name],
                            rel_gap(getattr(tr, field), reference[key]))
    out["trace_layout_mismatch"] = float(mismatch)
    return out


def judge(values: dict, limits: dict,
          numbers: tuple = NUMBERS) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers that `limits`
    names, of the cell's kind's `numbers`; a cell's limits file leaves out
    a number that cannot tell a sound run from the control (PERF.md says
    which and why)."""
    unknown = set(limits) - set(numbers)
    if unknown:
        raise KeyError(f"limits for unknown numbers {sorted(unknown)}")
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in numbers if name in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
