"""DDA correctness: the prox map, convergence on convex problems, schedule
effects, compression, and the simulated time model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DDASimulator, EveryIteration, IncreasinglySparse,
                        Periodic, complete_graph, dda_init, dda_local_step,
                        ring_graph, stepsize_sqrt)


def _quadratic_problem(n=6, d=8, seed=0):
    """f_i(x) = ||x - c_i||^2; F minimized at mean(c_i)."""
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)

    def subgrad(x_stack, t, key):
        return 2.0 * (x_stack - c)

    def objective(x):
        return jnp.mean(jnp.sum((x[None, :] - c) ** 2, axis=1))

    return subgrad, objective, c


def test_prox_step_solves_argmin():
    """x = argmin <z,x> + ||x||^2/(2a)  <=>  x = -a z (psi = l2/2)."""
    z = jnp.asarray(np.random.default_rng(0).normal(size=(5,)), jnp.float32)
    a = 0.37
    x = -a * z
    # numerical check: objective at x is lower than at x + perturbations
    obj = lambda y: jnp.dot(z, y) + jnp.sum(y * y) / (2 * a)
    base = obj(x)
    for _ in range(10):
        pert = 0.01 * np.random.default_rng(1).normal(size=(5,))
        assert obj(x + jnp.asarray(pert, jnp.float32)) >= base - 1e-6


@pytest.mark.parametrize("topology", ["complete", "ring"])
def test_dda_converges_quadratic(topology):
    n, d = 6, 8
    subgrad, objective, c = _quadratic_problem(n, d)
    graph = complete_graph(n) if topology == "complete" else ring_graph(n)
    sim = DDASimulator(subgrad, jax.jit(objective), graph,
                       EveryIteration(), a_fn=stepsize_sqrt(0.05))
    trace = sim.run(jnp.zeros((n, d)), 600, eval_every=100)
    fstar = float(objective(jnp.mean(c, axis=0)))
    assert trace.fvals[-1] < fstar * 1.05 + 1e-6
    assert trace.fvals[-1] < trace.fvals[0]


def test_dda_periodic_converges_slower_but_converges():
    n, d = 6, 8
    subgrad, objective, c = _quadratic_problem(n, d)
    fstar = float(objective(jnp.mean(c, axis=0)))
    sims = {}
    for name, sched in (("h1", EveryIteration()), ("h5", Periodic(h=5))):
        sim = DDASimulator(subgrad, jax.jit(objective), complete_graph(n),
                           sched, a_fn=stepsize_sqrt(0.05))
        sims[name] = sim.run(jnp.zeros((n, d)), 400, eval_every=400)
    assert sims["h1"].fvals[-1] < fstar * 1.1
    assert sims["h5"].fvals[-1] < fstar * 1.2  # still converges
    assert sims["h5"].comms[-1] < sims["h1"].comms[-1] / 4


def test_dda_sparse_schedule_converges():
    n, d = 6, 8
    subgrad, objective, c = _quadratic_problem(n, d)
    fstar = float(objective(jnp.mean(c, axis=0)))
    sim = DDASimulator(subgrad, jax.jit(objective), complete_graph(n),
                       IncreasinglySparse(p=0.3), a_fn=stepsize_sqrt(0.05))
    tr = sim.run(jnp.zeros((n, d)), 600, eval_every=600)
    assert tr.fvals[-1] < fstar * 1.1


def test_dda_with_compression_converges():
    n, d = 6, 16
    subgrad, objective, c = _quadratic_problem(n, d)
    fstar = float(objective(jnp.mean(c, axis=0)))
    sim = DDASimulator(subgrad, jax.jit(objective), complete_graph(n),
                       EveryIteration(), a_fn=stepsize_sqrt(0.05),
                       compress_keep=0.25)
    tr = sim.run(jnp.zeros((n, d)), 800, eval_every=800)
    assert tr.fvals[-1] < fstar * 1.15


def test_time_model_accounting():
    n, d = 4, 4
    subgrad, objective, _ = _quadratic_problem(n, d)
    g = complete_graph(n)
    r = 0.01
    sim = DDASimulator(subgrad, jax.jit(objective), g, Periodic(h=3),
                       a_fn=stepsize_sqrt(0.05), r=r)
    tr = sim.run(jnp.zeros((n, d)), 90, eval_every=90)
    H = (90 - 1) // 3
    expected = 90 * (1 / n) + H * g.degree * r
    assert np.isclose(tr.sim_time[-1], expected, rtol=1e-6)
    assert tr.comms[-1] == H


def test_dda_local_step_pure():
    x0 = {"w": jnp.ones((3,))}
    state = dda_init(x0)
    grad = {"w": jnp.full((3,), 2.0)}
    a_fn = stepsize_sqrt(0.1)
    s1 = dda_local_step(state, grad, a_fn)
    np.testing.assert_allclose(np.asarray(s1.z["w"]), 2.0)
    np.testing.assert_allclose(np.asarray(s1.x["w"]), -0.1 * 2.0, rtol=1e-6)
    # running average after first step equals x(1)
    np.testing.assert_allclose(np.asarray(s1.xhat["w"]),
                               np.asarray(s1.x["w"]), rtol=1e-6)


def test_disagreement_decreases_with_communication():
    n, d = 8, 8
    subgrad, objective, _ = _quadratic_problem(n, d, seed=3)
    out = {}
    for name, sched in (("every", EveryIteration()), ("h10", Periodic(h=10))):
        sim = DDASimulator(subgrad, jax.jit(objective), ring_graph(n), sched,
                           a_fn=stepsize_sqrt(0.05))
        out[name] = sim.run(jnp.zeros((n, d)), 200, eval_every=200)
    assert out["every"].disagreement[-1] < out["h10"].disagreement[-1]


# ---------------------------------------------------------------------------
# device-resident fast path: scanned loop, sparse gossip, vmapped batch
# ---------------------------------------------------------------------------


def _expander(n, k=4, seed=0):
    from repro.core.graphs import kregular_expander
    return kregular_expander(n, k=k, seed=seed)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)))


@pytest.mark.parametrize("sched", [EveryIteration(), Periodic(h=3),
                                   IncreasinglySparse(p=0.3)],
                         ids=["every", "h3", "p03"])
def test_scan_loop_matches_segment_loop(sched):
    """The fully-scanned run == the legacy per-segment dispatch loop on the
    same simulator: identical time axis and comm counts, fvals equal to
    float-fusion tolerance (eval moves inside jit). Covers a partial final
    segment (T % eval_every != 0)."""
    n, d = 6, 12
    subgrad, objective, _ = _quadratic_problem(n, d)
    sim = DDASimulator(subgrad, jax.jit(objective), _expander(n, k=2),
                       sched, a_fn=stepsize_sqrt(0.05), r=0.02)
    seg = sim.run(jnp.zeros((n, d)), 103, eval_every=25, loop="segment")
    scan = sim.run(jnp.zeros((n, d)), 103, eval_every=25, loop="scan")
    assert seg.iters == scan.iters
    assert seg.sim_time == scan.sim_time
    assert seg.comms == scan.comms
    assert _rel(seg.fvals, scan.fvals) < 1e-5
    assert _rel(seg.fvals_consensus, scan.fvals_consensus) < 1e-5


def test_sparse_mix_matches_dense_on_expander():
    """The gather+fused sparse gossip path reproduces the dense-matmul mix
    on a seeded expander run to <= 1e-5 relative (the acceptance gate's
    tolerance; float accumulation order differs)."""
    n, d = 12, 24
    subgrad, objective, _ = _quadratic_problem(n, d, seed=1)
    traces = {}
    for mix in ("dense", "sparse"):
        sim = DDASimulator(subgrad, jax.jit(objective), _expander(n),
                           EveryIteration(), a_fn=stepsize_sqrt(0.05),
                           mix=mix)
        assert sim.mix_mode == mix
        traces[mix] = sim.run(jnp.zeros((n, d)), 150, eval_every=30)
    assert _rel(traces["dense"].fvals, traces["sparse"].fvals) < 1e-5
    assert traces["dense"].comms == traces["sparse"].comms


def test_sparse_mix_weights_matches_dense_weighted():
    """A reweighted edge-supported P (`mix_weights`, the
    reweight_gossip shape) runs through the sparse per-edge path and
    matches the dense matmul with the same W."""
    n, d = 10, 16
    subgrad, objective, _ = _quadratic_problem(n, d, seed=2)
    g = _expander(n)
    rng = np.random.default_rng(0)
    W = g.mixing_matrix()
    # perturb edge weights, fold the correction into the diagonal so rows
    # stay stochastic (shape-wise; exact stochasticity is not required)
    for i in range(n):
        for j in range(n):
            if i != j and W[i, j] != 0.0:
                delta = rng.uniform(-0.3, 0.3) * W[i, j]
                W[i, j] += delta
                W[i, i] -= delta
    traces = {}
    for mix in ("dense", "sparse"):
        sim = DDASimulator(subgrad, jax.jit(objective), g, Periodic(h=2),
                           a_fn=stepsize_sqrt(0.05), mix=mix,
                           mix_weights=W)
        assert sim.mix_mode == mix
        traces[mix] = sim.run(jnp.zeros((n, d)), 120, eval_every=30)
    assert _rel(traces["dense"].fvals, traces["sparse"].fvals) < 1e-5


def test_auto_mix_fallbacks():
    """auto -> dense for complete graphs and for a mix_weights with weight
    OUTSIDE the graph's edge support (non-regular P); forcing mix="sparse"
    there raises. Compression does NOT disqualify sparse: compressed
    messages ride the fused compress-mix gather."""
    n, d = 8, 8
    subgrad, objective, _ = _quadratic_problem(n, d)
    g = _expander(n)
    obj = jax.jit(objective)
    assert DDASimulator(subgrad, obj, g, EveryIteration()).mix_mode \
        == "sparse"
    assert DDASimulator(subgrad, obj, complete_graph(n),
                        EveryIteration()).mix_mode == "dense"
    assert DDASimulator(subgrad, obj, g, EveryIteration(),
                        compress_keep=0.5).mix_mode == "sparse"
    W = g.mixing_matrix()
    W[0, :] = 1.0 / n  # weight on non-edges: not gatherable along edges
    sim = DDASimulator(subgrad, obj, g, EveryIteration(), mix_weights=W)
    assert sim.mix_mode == "dense"
    with pytest.raises(ValueError, match="edge support"):
        DDASimulator(subgrad, obj, g, EveryIteration(), mix_weights=W,
                     mix="sparse")
    # the dense fallback actually APPLIES the override
    tr_w = sim.run(jnp.zeros((n, d)), 40, eval_every=40)
    tr_p = DDASimulator(subgrad, obj, g, EveryIteration()).run(
        jnp.zeros((n, d)), 40, eval_every=40)
    assert tr_w.fvals != tr_p.fvals


def test_scan_loop_empty_run():
    """T=0 returns an empty trace on every loop, as the legacy path did."""
    n, d = 4, 4
    subgrad, objective, _ = _quadratic_problem(n, d)
    sim = DDASimulator(subgrad, jax.jit(objective), _expander(n, k=2))
    for loop in ("scan", "segment"):
        tr = sim.run(jnp.zeros((n, d)), 0, eval_every=10, loop=loop)
        assert tr.iters == [] and tr.fvals == []
    batch = sim.run_batch(jnp.zeros((n, d)), 0, 10,
                          np.zeros((2, 0), bool), seeds=[0, 1])
    assert all(tr.iters == [] for tr in batch)


def test_run_batch_matches_single_runs():
    """One vmapped program over (schedule, seed, r) lanes == the per-lane
    scanned runs, bitwise (same program, batched)."""
    n, d, T = 6, 10, 77
    subgrad, objective, _ = _quadratic_problem(n, d)
    sim = DDASimulator(subgrad, jax.jit(objective), _expander(n, k=2),
                       a_fn=stepsize_sqrt(0.05))
    scheds = [EveryIteration(), Periodic(h=2), Periodic(h=5)]
    masks = np.stack([s.comm_mask(0, T) for s in scheds])
    seeds, rs = [0, 1, 2], [0.0, 0.01, 0.1]
    batch = sim.run_batch(jnp.zeros((n, d)), T, 25, masks, seeds, rs)
    for sched, seed, r, btr in zip(scheds, seeds, rs, batch):
        one = DDASimulator(subgrad, jax.jit(objective), _expander(n, k=2),
                           sched, a_fn=stepsize_sqrt(0.05), r=r)
        tr = one.run(jnp.zeros((n, d)), T, eval_every=25, seed=seed)
        assert btr.iters == tr.iters
        assert btr.sim_time == tr.sim_time
        assert btr.comms == tr.comms
        assert _rel(btr.fvals, tr.fvals) < 1e-6
        assert _rel(btr.disagreement, tr.disagreement) < 1e-5


@pytest.mark.parametrize("schedule", [{"kind": "every"},
                                      {"kind": "periodic",
                                       "params": {"h": 4}}])
def test_compiled_programs_take_problem_data_as_arguments(schedule):
    """The problem's arrays reach the compiled scan as arguments, not as
    constants baked into the executable -- neither the subgradient's nor
    the (jitted) objective's. Baked in, the non-smooth problem's center
    tensor makes every program as large as its data: a minute of compile
    per program on a TPU and a cache entry no compile cache admits."""
    import re

    from repro.experiments import ExperimentSpec
    from repro.experiments.runner import _dense_parts, _dense_sim

    n, M, d = 16, 5, 256
    spec = ExperimentSpec(
        name="hoist", problem={"kind": "nonsmooth",
                               "params": {"n": n, "M": M, "d": d}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule=schedule, backends=[{"kind": "dense"}], T=50,
        eval_every=25, seed=0)
    sim = _dense_sim(spec, _dense_parts(spec, spec.backends[0]))
    sim.run(jnp.zeros((n, d)), spec.T, eval_every=spec.eval_every)
    (exe,) = sim._compiled.values()
    sizes = [int(np.prod([int(v) for v in dims.split(",")]))
             for dims in re.findall(r"\[([\d,]+)\][^=\n]*constant\(",
                                    exe.as_text())]
    assert max(sizes, default=0) < n * M * 2 * d // 8


_EXPANDER = {"kind": "expander", "params": {"k": 4, "seed": 0}}
_NONSMOOTH = {"kind": "nonsmooth", "params": {"n": 16, "M": 4, "d": 128}}
_LAYERS = {"dda.subgrad", "dda.mix", "dda.update", "dda.evaluate"}


@pytest.mark.parametrize("case,spec_fields,module,scopes", [
    ("every", dict(problem=_NONSMOOTH, topology=_EXPANDER,
                   schedule={"kind": "every"}),
     "jit_dda_scan_allcomm", _LAYERS),
    ("periodic", dict(problem=_NONSMOOTH, topology=_EXPANDER,
                      schedule={"kind": "periodic", "params": {"h": 4}}),
     "jit_dda_scan_cond", _LAYERS),
    ("topk", dict(problem=_NONSMOOTH, topology=_EXPANDER,
                  schedule={"kind": "every"},
                  compression={"kind": "topk", "params": {"keep": 0.125}}),
     "jit_dda_scan_allcomm", _LAYERS | {"dda.compress"}),
    ("projection", dict(problem={"kind": "metric_learning",
                                 "params": {"n": 3, "m_pairs": 60,
                                            "d_feat": 6}},
                        topology={"kind": "complete"},
                        schedule={"kind": "every"}),
     "jit_dda_scan_allcomm", _LAYERS | {"dda.project"}),
])
def test_compiled_program_names_its_layers(case, spec_fields, module,
                                           scopes):
    """The scan program's ops carry the simulator's named scopes in their
    metadata, which a device trace keeps per op, and its module is named
    for the program kind."""
    import re

    from repro.experiments import ExperimentSpec
    from repro.experiments.runner import _dense_parts, _dense_sim

    spec = ExperimentSpec(name=case, backends=[{"kind": "dense"}], T=20,
                          eval_every=10, seed=0, **spec_fields)
    parts = _dense_parts(spec, spec.backends[0])
    sim = _dense_sim(spec, parts)
    sim.run(jnp.zeros((parts["problem"].n, parts["problem"].d)), spec.T,
            eval_every=spec.eval_every)
    (exe,) = sim._compiled.values()
    text = exe.as_text()
    assert text.startswith(f"HloModule {module},")
    root = f"jit({module.removeprefix('jit_')})/"
    names = [name for name in re.findall(r'op_name="([^"]*)"', text)
             if name.startswith(root)]
    found = {part for name in names for part in name.split("/")
             if part.startswith("dda.")}
    assert found == scopes
    assert "<unknown>" not in text
