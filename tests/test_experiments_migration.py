"""Migration gate: spec-driven runs == pre-redesign hand-wired runs, bitwise.

The api_redesign moved benchmarks/fig_async.py, benchmarks/fig_adaptive.py
and benchmarks/bench_netsim.py (plus the examples) onto
`ExperimentSpec -> repro.run()`. These tests reconstruct each driver's
PRE-redesign wiring -- direct NetSimulator / DDASimulator / controller
assembly, exactly as the seeded drivers built it before the migration --
and assert the new spec path reproduces the traces BIT-IDENTICALLY
(`SimTrace` field equality, plus `RMeasurement` equality where measured).
The netsim engines are deterministic numpy, so equality here is
machine-independent; the dense comparison runs both paths in-process
against the same jit cache.
"""

import math

import numpy as np
import pytest

from repro.core.dda import TRACE_FIELDS
from repro.experiments import ExperimentSpec, run


def _assert_traces_equal(a, b, what=""):
    for f in TRACE_FIELDS:
        assert getattr(a, f) == getattr(b, f), f"{what}: {f} differs"


# ---------------------------------------------------------------------------
# fig_async cells
# ---------------------------------------------------------------------------

FIG_ASYNC = dict(n=8, M=10, d=6, seed=0, T=250, r=0.01, eval_every=2, k=4)


def _legacy_async_cell(scenario, schedule, algorithm="dda"):
    """The pre-redesign fig_async.run_cell wiring, verbatim."""
    from repro.data.pipeline import nonsmooth_quadratic_problem
    from repro.netsim import NetSimulator

    g = FIG_ASYNC
    centers = nonsmooth_quadratic_problem(
        g["n"], g["M"], g["d"], g["seed"], center_scale=1.5
    ).astype(np.float64)

    def grad_fn(i, x, t):
        diff = x[None, None, :] - centers[i]
        q = np.sum(diff * diff, axis=-1)
        pick = np.argmax(q, axis=-1)
        chosen = np.take_along_axis(diff, pick[:, None, None], axis=1)[:, 0]
        return 2.0 * np.sum(chosen, axis=0)

    def eval_fn(x):
        diff = x[None, None, None, :] - centers
        q = np.sum(diff * diff, axis=-1)
        return float(np.mean(np.sum(np.max(q, axis=-1), axis=-1)))

    a_scale = 1.0 / (4.0 * g["M"])
    a_fn = (lambda t: a_scale / math.sqrt(max(t, 1.0)))
    sim = NetSimulator(scenario, grad_fn, eval_fn, a_fn=a_fn,
                       schedule=schedule, algorithm=algorithm,
                       seed=g["seed"])
    trace = sim.run(np.zeros((g["n"], g["d"])), g["T"],
                    eval_every=g["eval_every"])
    return sim, trace


def _async_spec(scenario_kind, knobs, schedule):
    g = FIG_ASYNC
    return ExperimentSpec(
        name="mig",
        problem={"kind": "nonsmooth",
                 "params": {"n": g["n"], "M": g["M"], "d": g["d"],
                            "seed": g["seed"]}},
        topology={"kind": "expander",
                  "params": {"k": g["k"], "seed": g["seed"]}},
        schedule=schedule,
        backends=[{"kind": "netsim",
                   "params": {"scenario": scenario_kind, **knobs}}],
        stepsize={"kind": "inv_sqrt",
                  "params": {"A": 1.0 / (4.0 * g["M"])}},
        T=g["T"], eval_every=g["eval_every"], seed=g["seed"], r=g["r"])


@pytest.mark.parametrize("cell", [
    ("homogeneous", {}, {"kind": "every"}),
    ("lossy", {"loss": 0.2}, {"kind": "every"}),
    ("straggler", {"slow_factor": 4.0}, {"kind": "periodic",
                                         "params": {"h": 2}}),
    ("adversarial", {"loss": 0.1, "slow_factor": 2.0},
     {"kind": "sparse", "params": {"p": 0.3}}),
], ids=lambda c: c[0] if isinstance(c, tuple) else str(c))
def test_fig_async_cells_bit_identical(cell):
    scenario_kind, knobs, schedule_comp = cell
    from repro.core import make_schedule
    from repro.netsim import adversarial, homogeneous, lossy, straggler

    g = FIG_ASYNC
    legacy_scenario = {
        "homogeneous": lambda: homogeneous(g["n"], g["r"], k=g["k"],
                                           seed=g["seed"]),
        "lossy": lambda: lossy(g["n"], g["r"], loss=0.2, k=g["k"],
                               seed=g["seed"]),
        "straggler": lambda: straggler(g["n"], g["r"], slow_factor=4.0,
                                       k=g["k"], seed=g["seed"]),
        "adversarial": lambda: adversarial(g["n"], g["r"], loss=0.1,
                                           slow_factor=2.0, k=g["k"],
                                           seed=g["seed"]),
    }[scenario_kind]()
    sched_kind = schedule_comp["kind"]
    legacy_sched = make_schedule(
        sched_kind, **schedule_comp.get("params", {}))
    sim, legacy_trace = _legacy_async_cell(legacy_scenario, legacy_sched)

    res = run(_async_spec(scenario_kind, knobs, schedule_comp))
    _assert_traces_equal(legacy_trace, res.trace, f"fig_async {scenario_kind}")
    assert sim.measure_r_empirical() == res.r_measurement


def test_fig_async_pushsum_cell_bit_identical():
    from repro.core import make_schedule
    from repro.netsim import lossy

    g = FIG_ASYNC
    sc = lossy(g["n"], g["r"], loss=0.3, k=g["k"], seed=g["seed"])
    _, legacy_trace = _legacy_async_cell(sc, make_schedule("every"),
                                         algorithm="pushsum")
    spec = _async_spec("lossy", {"loss": 0.3, "algorithm": "pushsum"},
                       {"kind": "every"})
    res = run(spec)
    _assert_traces_equal(legacy_trace, res.trace, "fig_async pushsum")


# ---------------------------------------------------------------------------
# fig_adaptive cells (fixed grid + the closed loop)
# ---------------------------------------------------------------------------

FIG_AD = dict(n=8, d=6, seed=0, T=600, r=1.3, eval_every=10, k=8,
              loss=0.2, straggler=4.0, n_slow=2, a_scale=0.5,
              time_limit=3000.0)


def _legacy_adaptive_run(schedule=None, ctrl=None, engine="auto"):
    """The pre-redesign fig_adaptive.run_one wiring, verbatim."""
    from repro.netsim import NetSimulator, adversarial, quadratic_consensus

    g = FIG_AD
    _, grad_fn, eval_fn = quadratic_consensus(g["n"], g["d"],
                                              seed=g["seed"])
    sc = adversarial(g["n"], g["r"], loss=g["loss"],
                     slow_factor=g["straggler"], n_slow=g["n_slow"],
                     k=g["k"], seed=g["seed"])
    a_fn = (lambda t: g["a_scale"] / math.sqrt(max(t, 1.0)))
    sim = NetSimulator(sc, grad_fn, eval_fn, a_fn=a_fn, schedule=schedule,
                       controller=ctrl, seed=g["seed"], engine=engine)
    trace = sim.run(np.zeros((g["n"], g["d"])), g["T"],
                    eval_every=g["eval_every"],
                    time_limit=g["time_limit"])
    return sim, trace


def _adaptive_spec(schedule, controller=None, engine="auto"):
    g = FIG_AD
    return ExperimentSpec(
        name="mig-adaptive",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": g["n"], "d": g["d"], "seed": g["seed"]}},
        topology={"kind": "expander",
                  "params": {"k": g["k"], "seed": g["seed"]}},
        schedule=schedule,
        controller=controller,
        backends=[{"kind": "netsim",
                   "params": {"scenario": "adversarial", "loss": g["loss"],
                              "slow_factor": g["straggler"],
                              "n_slow": g["n_slow"], "engine": engine}}],
        stepsize={"kind": "inv_sqrt", "params": {"A": g["a_scale"]}},
        T=g["T"], eval_every=g["eval_every"], seed=g["seed"], r=g["r"],
        time_limit=g["time_limit"])


@pytest.mark.parametrize("h", [1, 4])
def test_fig_adaptive_fixed_cells_bit_identical(h):
    from repro.core.schedules import Periodic

    _, legacy_trace = _legacy_adaptive_run(schedule=Periodic(h=h))
    res = run(_adaptive_spec({"kind": "periodic", "params": {"h": h}}))
    _assert_traces_equal(legacy_trace, res.trace, f"fig_adaptive h={h}")


@pytest.mark.parametrize("engine", ["object", "vectorized"])
def test_fig_adaptive_closed_loop_bit_identical(engine):
    from repro.adaptive import AdaptiveController, AdaptiveSchedule

    ctrl = AdaptiveController(AdaptiveSchedule(h0=1, p=0.1),
                              update_every=0.5, warmup_messages=4,
                              warmup_steps=4)
    _, legacy_trace = _legacy_adaptive_run(ctrl=ctrl, engine=engine)
    res = run(_adaptive_spec(
        {"kind": "adaptive", "params": {"h0": 1, "p": 0.1}},
        controller={"kind": "adaptive",
                    "params": {"update_every": 0.5, "warmup_messages": 4,
                               "warmup_steps": 4}},
        engine=engine))
    _assert_traces_equal(legacy_trace, res.trace,
                         f"fig_adaptive closed-loop {engine}")
    assert res.extras["retunes"] == [(rt.from_t, rt.h)
                                     for rt in ctrl.schedule.retunes]


# ---------------------------------------------------------------------------
# bench_netsim cells
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["object", "vectorized"])
@pytest.mark.parametrize("algorithm", ["dda", "pushsum"])
def test_bench_netsim_cells_bit_identical(engine, algorithm):
    """The pre-redesign bench_cell wiring (batchable problem, default
    stepsize) against the spec path used by the migrated bench."""
    from benchmarks.bench_netsim import cell_spec
    from repro.netsim import NetSimulator, homogeneous, quadratic_consensus

    n, d, T, r, k, seed, ev = 16, 8, 40, 0.01, 4, 0, 5
    _, grad_fn, eval_fn = quadratic_consensus(n, d, seed, batchable=True)
    sc = homogeneous(n, r, k=k, seed=seed)
    sim = NetSimulator(sc, grad_fn, eval_fn, algorithm=algorithm,
                       seed=seed, engine=engine)
    legacy_trace = sim.run(np.zeros((n, d)), T=T, eval_every=ev)

    res = run(cell_spec(n, d, T, r, k, algorithm, engine, seed, ev))
    _assert_traces_equal(legacy_trace, res.trace,
                         f"bench {algorithm}/{engine}")
    assert res.extras["sent"] == sim.sent


# ---------------------------------------------------------------------------
# dense driver (fig1/fig2-style DDASimulator wiring)
# ---------------------------------------------------------------------------


def test_dense_cell_bit_identical():
    import jax
    import jax.numpy as jnp

    from repro.core import DDASimulator, Periodic, complete_graph
    from repro.core.dda import stepsize_sqrt
    from repro.experiments.components import problems

    n, d, T, seed = 10, 8, 150, 0
    prob = problems.build("quadratic_consensus", n=n, d=d, seed=seed)
    sim = DDASimulator(prob.subgrad_stack, jax.jit(prob.objective),
                       complete_graph(n), Periodic(h=2),
                       a_fn=stepsize_sqrt(0.5), r=0.01)
    legacy_trace = sim.run(jnp.zeros((n, d)), T, eval_every=15, seed=seed)

    spec = ExperimentSpec(
        name="mig-dense",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": n, "d": d, "seed": seed}},
        topology={"kind": "complete"},
        schedule={"kind": "periodic", "params": {"h": 2}},
        backends=[{"kind": "dense"}],
        stepsize={"kind": "sqrt", "params": {"A": 0.5}},
        T=T, eval_every=15, seed=seed, r=0.01)
    res = run(spec)
    _assert_traces_equal(legacy_trace, res.trace, "dense")


# ---------------------------------------------------------------------------
# fig1 (metric learning, complete graph) + fig2 (non-smooth schedules):
# the last pre-spec drivers, migrated onto manifests in this PR. The legacy
# side reconstructs the direct DDASimulator wiring the drivers used (same
# registry problem closures, same stepsize family), the spec side goes
# through the migrated drivers' cell_spec + repro.run().
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("keep", [None, 0.5], ids=["exact", "compressed"])
def test_fig1_cells_bit_identical(keep):
    import jax
    import jax.numpy as jnp

    from benchmarks.fig1_complete import cell_spec
    from repro.compress import TopK
    from repro.core import DDASimulator, EveryIteration, complete_graph
    from repro.core.dda import stepsize_sqrt
    from repro.experiments.components import problems

    n, m_pairs, d, T, seed, r, A = 4, 400, 6, 40, 0, 0.03, 3e-4
    prob = problems.build("metric_learning", n=n, m_pairs=m_pairs,
                          d_feat=d, seed=seed)
    sim = DDASimulator(prob.subgrad_stack, jax.jit(prob.objective),
                       complete_graph(n), EveryIteration(),
                       a_fn=stepsize_sqrt(A), projection=prob.projection,
                       r=r, compression=None if keep is None
                       else TopK(keep=keep))
    legacy_trace = sim.run(jnp.zeros((n, prob.d)), T, eval_every=10,
                           seed=seed)

    res = run(cell_spec(n, m_pairs, d, T, A, r, seed, keep=keep))
    _assert_traces_equal(legacy_trace, res.trace, f"fig1 keep={keep}")


def test_fig1_reduced_applies_byte_ratio():
    """fig1_reduced is fig1_complete at r scaled by the paper's PCA byte
    ratio -- the spec cell only differs in the r field."""
    from benchmarks import fig1_reduced
    from benchmarks.fig1_complete import cell_spec

    a = cell_spec(4, 400, 6, 40, 3e-4, 0.03, 0)
    b = cell_spec(4, 400, 6, 40, 3e-4,
                  0.03 * fig1_reduced.PCA_BYTE_RATIO, 0)
    assert b.r == pytest.approx(a.r * fig1_reduced.PCA_BYTE_RATIO)
    assert a.with_value("r", b.r) == b


@pytest.mark.parametrize("sched_name", ["h1", "h2", "p03"])
def test_fig2_cells_bit_identical(sched_name):
    import jax
    import jax.numpy as jnp

    from benchmarks.fig2_sparse import SCHEDULES, cell_spec
    from repro.core import DDASimulator, complete_graph
    from repro.core.dda import stepsize_sqrt
    from repro.experiments.components import problems

    n, M, d, T, seed, r, A = 6, 8, 10, 80, 0, 0.00089, 0.005
    sched_comp, sched_obj = SCHEDULES[sched_name]
    prob = problems.build("nonsmooth", n=n, M=M, d=d, seed=seed)
    sim = DDASimulator(prob.subgrad_stack, jax.jit(prob.objective),
                       complete_graph(n), sched_obj,
                       a_fn=stepsize_sqrt(A), r=r)
    legacy_trace = sim.run(jnp.zeros((n, d)), T, eval_every=20, seed=seed)

    res = run(cell_spec(n, M, d, T, sched_comp, A, r, seed))
    _assert_traces_equal(legacy_trace, res.trace, f"fig2 {sched_name}")
