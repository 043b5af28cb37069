"""The unified experiment API: registries, spec serialization, run().

Covers the redesign's contracts:
  * ExperimentSpec <-> JSON round-trip is EXACT (seeded + property-based);
  * a spec that went through JSON runs IDENTICALLY to the original, on the
    homogeneous and adversarial presets, on both netsim engines;
  * the checked-in manifests under benchmarks/manifests/ load, round-trip,
    and run on every backend they declare;
  * run_sweep replaces dotted-path axes correctly;
  * make_schedule routes through the schedule registry (and can now build
    PiecewisePeriodic / AdaptiveSchedule);
  * the dense_adaptive controller retunes h from (injected) wall-clock
    timings; the reweight_gossip flag applies the effective P to the actual
    stale mix and still converges.
"""

import json
import math
import pathlib

import numpy as np
import pytest

import repro
from repro.core import schedules as S
from repro.core.dda import TRACE_FIELDS
from repro.experiments import (ComponentSpec, ExperimentSpec, RunResult,
                               backends, problems, run, run_all, run_sweep,
                               schedules, stepsizes, topologies)
from tests._hyp import HAVE_HYPOTHESIS, given, st

MANIFESTS = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "manifests"


def tiny_netsim_spec(scenario="homogeneous", engine="auto", **knobs):
    return ExperimentSpec(
        name="tiny",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": 8, "d": 4, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule={"kind": "periodic", "params": {"h": 2}},
        backends=[{"kind": "netsim",
                   "params": {"scenario": scenario, "engine": engine,
                              **knobs}}],
        stepsize={"kind": "inv_sqrt", "params": {"A": 0.5}},
        T=120, eval_every=10, seed=0, r=0.05, eps_frac=0.1)


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------


def test_registry_names_and_errors():
    assert "quadratic_consensus" in problems
    assert "quadratic" in problems  # alias
    assert "complete" in topologies
    assert {"every", "periodic", "sparse", "piecewise",
            "adaptive"} <= set(schedules.names())
    assert set(backends.names()) == {"dense", "launch", "netsim"}
    with pytest.raises(KeyError, match="unknown topology"):
        topologies.build("nope", n=4)


def test_registry_rejects_duplicate_registration():
    with pytest.raises(ValueError):
        schedules.register("periodic")(lambda: None)


def test_make_schedule_routes_through_registry():
    # the legacy kinds keep their legacy defaults...
    assert isinstance(S.make_schedule("every"), S.EveryIteration)
    assert S.make_schedule("periodic", h=4).h == 4
    assert S.make_schedule("sparse", p=0.2).p == 0.2
    # ...and the kinds the ad-hoc branching could NOT build now work
    pw = S.make_schedule("piecewise", h=3)
    assert isinstance(pw, S.PiecewisePeriodic) and pw.h_current == 3
    from repro.adaptive import AdaptiveSchedule
    ad = S.make_schedule("adaptive", h0=2, p=0.1)
    assert isinstance(ad, AdaptiveSchedule) and ad.h_current == 2
    assert ad.p == 0.1  # the named p kwarg must reach kinds that take it
    # legacy tolerance: kinds ignore the legacy knobs they never took...
    assert isinstance(S.make_schedule("every", h=5), S.EveryIteration)
    # ...but non-legacy kwargs fail loudly
    with pytest.raises(TypeError):
        S.make_schedule("periodic", hh=3)
    with pytest.raises(ValueError):
        S.make_schedule("nope")


# ---------------------------------------------------------------------------
# spec serialization
# ---------------------------------------------------------------------------


def test_spec_json_round_trip_exact():
    spec = tiny_netsim_spec("adversarial", loss=0.2, slow_factor=4.0,
                            n_slow=2)
    text = spec.to_json()
    again = ExperimentSpec.from_json(text)
    assert again == spec
    assert again.to_json() == text  # fixed point


def test_spec_normalizes_tuples_and_numpy_scalars():
    spec = ComponentSpec("expander", {"k": np.int64(4),
                                      "shifts": (1, 2)})
    assert spec.params == {"k": 4, "shifts": [1, 2]}
    assert isinstance(spec.params["k"], int)


def test_spec_rejects_non_json_params():
    with pytest.raises(TypeError, match="not JSON-serializable"):
        ComponentSpec("x", {"fn": lambda: None})
    with pytest.raises(TypeError, match="not JSON-serializable"):
        ComponentSpec("x", {"arr": np.zeros(3)})


def test_spec_rejects_unknown_keys_and_versions():
    d = tiny_netsim_spec().to_dict()
    with pytest.raises(ValueError, match="unknown keys"):
        ExperimentSpec.from_dict({**d, "spam": 1})
    with pytest.raises(ValueError, match="spec_version"):
        ExperimentSpec.from_dict({**d, "spec_version": 99})


def test_with_value_axes():
    spec = tiny_netsim_spec()
    assert spec.with_value("T", 10).T == 10
    assert spec.with_value("schedule.params.h", 7).schedule.params["h"] == 7
    assert spec.with_value("topology.kind", "ring").topology.kind == "ring"
    s2 = spec.with_value("backends.0.params.engine", "object")
    assert s2.backends[0].params["engine"] == "object"
    with pytest.raises(KeyError, match="axis"):
        spec.with_value("nonsense_field", 3)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@given(st.integers(2, 32), st.integers(1, 8), st.integers(0, 2 ** 31),
       st.floats(0.0, 10.0, allow_nan=False),
       st.sampled_from(["every", "periodic", "sparse"]))
def test_spec_round_trip_property(n, h, seed, r, kind):
    spec = ExperimentSpec(
        name="prop",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": n, "d": 3, "seed": seed}},
        topology={"kind": "expander", "params": {"k": 4, "seed": seed}},
        schedule={"kind": kind,
                  "params": ({"h": h} if kind == "periodic" else {})},
        backends=[{"kind": "netsim"}],
        T=10, seed=seed, r=r)
    assert ExperimentSpec.from_json(spec.to_json()) == spec


# ---------------------------------------------------------------------------
# run() determinism through serialization (the satellite gate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["object", "vectorized"])
@pytest.mark.parametrize("preset", [
    ("homogeneous", {}),
    ("adversarial", {"loss": 0.2, "slow_factor": 4.0, "n_slow": 2}),
])
def test_run_deterministic_through_json(engine, preset):
    """spec -> json -> spec -> run() must equal run(spec), bitwise, on the
    homogeneous and adversarial presets, both netsim engines."""
    scenario, knobs = preset
    spec = tiny_netsim_spec(scenario, engine=engine, **knobs)
    direct = run(spec)
    rehydrated = run(ExperimentSpec.from_json(spec.to_json()))
    for f in TRACE_FIELDS:
        assert getattr(direct.trace, f) == getattr(rehydrated.trace, f)
    assert direct.r_measurement == rehydrated.r_measurement
    assert direct.time_to_target == rehydrated.time_to_target


def test_netsim_engines_bit_identical_via_spec():
    spec = tiny_netsim_spec("adversarial", engine="object", loss=0.2,
                            slow_factor=4.0, n_slow=2)
    a = run(spec)
    b = run(spec.with_value("backends.0.params.engine", "vectorized"))
    for f in TRACE_FIELDS:
        assert getattr(a.trace, f) == getattr(b.trace, f)
    assert a.r_measurement == b.r_measurement


# ---------------------------------------------------------------------------
# RunResult
# ---------------------------------------------------------------------------


def test_run_result_round_trip():
    res = run(tiny_netsim_spec())
    again = RunResult.from_json(res.to_json())
    assert again.spec == res.spec
    assert again.backend == res.backend
    for f in TRACE_FIELDS:
        assert getattr(again.trace, f) == getattr(res.trace, f)
    assert again.r_measurement == res.r_measurement
    assert again.extras["engine"] == res.extras["engine"]
    # strict RFC: no Infinity/NaN tokens in the payload
    json.loads(res.to_json())


def test_run_result_unreached_target_is_null_not_inf():
    spec = tiny_netsim_spec()
    hard = ExperimentSpec.from_dict({**spec.to_dict(), "eps_frac": 1e-12,
                                     "T": 10})
    res = run(hard)
    assert res.time_to_target is None
    assert res.eps_value is not None
    json.loads(res.to_json())


# ---------------------------------------------------------------------------
# dispatch + sweeps
# ---------------------------------------------------------------------------


def test_run_backend_selection():
    spec = ExperimentSpec.from_dict({
        **tiny_netsim_spec(engine="object").to_dict(),
        "backends": [{"kind": "netsim", "params": {"engine": "object"}},
                     {"kind": "dense"}]})
    spec = ExperimentSpec.from_dict(
        {**spec.to_dict(), "stepsize": {"kind": "sqrt", "params": {"A": 0.5}}})
    by_default = run(spec)
    assert by_default.backend.kind == "netsim"
    by_index = run(spec, backend=1)
    assert by_index.backend.kind == "dense"
    by_kind = run(spec, backend="dense")
    assert by_kind.backend.kind == "dense"
    assert [r.backend.kind for r in run_all(spec)] == ["netsim", "dense"]
    with pytest.raises(KeyError, match="unknown backend"):
        run(spec, backend="cloud")


def test_run_sweep_h_grid():
    spec = tiny_netsim_spec()
    results = run_sweep(spec, "schedule.params.h", [1, 2, 4])
    assert [r.spec.schedule.params["h"] for r in results] == [1, 2, 4]
    # more communication rounds at smaller h, same iteration count
    comms = [r.trace.comms[-1] for r in results]
    assert comms[0] > comms[1] > comms[2]
    assert len({tuple(r.trace.iters) for r in results}) == 1


def test_backend_rejects_unknown_params_and_bad_combos():
    spec = tiny_netsim_spec()
    with pytest.raises(ValueError, match="unknown params"):
        run(spec.with_value("backends.0.params.typo_knob", 3))
    with pytest.raises(ValueError, match="host-only"):
        run(spec, backend="dense")  # inv_sqrt stepsize is netsim-only
    with pytest.raises(ValueError, match="expander_sequence"):
        run(tiny_netsim_spec("time_varying", rewire_every=1.0))
    with pytest.raises(KeyError, match="unknown scenario"):
        run(tiny_netsim_spec("marshy"))


@pytest.mark.parametrize("param,value", [("mix", "dense"),
                                         ("loop", "segment"),
                                         ("compress_keep", 0.5)],
                         ids=["mix", "loop", "compress_keep"])
def test_dense_backend_rejects_removed_params(param, value):
    """The dense backend takes no params: a manifest that still sets the
    mix, loop or compress_keep param of older revisions fails with the
    unknown-params error, in a solo run and in the lane packer's report,
    instead of being silently ignored."""
    from repro.experiments.runner import batch_compat_report

    d = json.loads(tiny_netsim_spec().to_json())
    d["stepsize"] = {"kind": "sqrt", "params": {"A": 0.5}}
    d["backends"] = [{"kind": "dense", "params": {param: value}}]
    spec = ExperimentSpec.from_json(json.dumps(d))
    with pytest.raises(ValueError, match=rf"unknown params \['{param}'\]"):
        run(spec)
    assert "unknown params" in batch_compat_report(spec, spec.backends[0])


# ---------------------------------------------------------------------------
# checked-in manifests: every declared backend runs
# ---------------------------------------------------------------------------


def _manifest_paths():
    return sorted(MANIFESTS.glob("*.json"))


def test_manifests_exist_for_every_figure_regime():
    names = {p.stem for p in _manifest_paths()}
    assert {"complete_every", "expander_periodic", "expander_sparse",
            "adaptive_adversarial", "launch_dryrun"} <= names


@pytest.mark.parametrize("path", _manifest_paths(), ids=lambda p: p.stem)
def test_manifest_round_trips_and_runs(path):
    spec = ExperimentSpec.from_file(path)
    # the checked-in file is exactly what the spec serializes back to
    assert json.loads(spec.to_json()) == json.loads(path.read_text())
    if spec.problem.kind == "lm":
        pytest.skip("launch manifest is exercised by "
                    "test_launch_dryrun_manifest (compile-heavy)")
    for result in run_all(spec):
        assert result.trace.iters, f"{path.stem}: empty trace"
        assert np.isfinite(result.trace.fvals).all()
        # declared netsim engines must agree bit-for-bit across the file
    netsims = [b for b in spec.backends if b.kind == "netsim"]
    if len(netsims) > 1:
        traces = [run(spec, backend=b).trace for b in netsims]
        for f in TRACE_FIELDS:
            vals = {tuple(getattr(t, f)) for t in traces}
            assert len(vals) == 1, f"engines disagree on {f}"


def test_launch_dryrun_manifest():
    """The launch backend's CI smoke: compile both step programs (cheap
    local + fused local+mix) for the smoke LM config on a 1-pod host mesh,
    run zero steps."""
    spec = ExperimentSpec.from_file(MANIFESTS / "launch_dryrun.json")
    res = run(spec)
    assert res.backend.kind == "launch"
    assert res.extras["dryrun"] is True
    assert res.extras["local_compile_s"] >= 0
    assert res.extras["fused_compile_s"] >= 0
    assert res.trace.iters == []  # zero steps by contract
    RunResult.from_json(res.to_json())


# ---------------------------------------------------------------------------
# dense_adaptive controller (DenseRTracker wiring)
# ---------------------------------------------------------------------------


def test_dense_adaptive_retunes_from_injected_timings(monkeypatch):
    """Drive the dense wall-clock loop with a fake timer that charges comm
    chunks heavily (r >> 0): the controller must measure that r and splice
    h upward -- deterministic, no real clock involved."""
    from repro.adaptive import AdaptiveSchedule, DenseController
    from repro.core import DDASimulator, complete_graph
    from repro.core.dda import stepsize_sqrt

    prob = problems.build("quadratic_consensus", n=8, d=4, seed=0)
    sched = AdaptiveSchedule(h0=1)
    sim = DDASimulator(prob.subgrad_stack, prob.objective,
                       complete_graph(8), sched,
                       a_fn=stepsize_sqrt(0.5), r=0.5)

    class FakeClock:
        """Advances by the charge of the LAST simulated chunk: plain
        iterations cost 1/n each, comm iterations 1/n + k * r_true."""
        def __init__(self):
            self.t = 0.0
            self.comm_next = False

        def __call__(self):
            return self.t

    clock = FakeClock()
    real_segment = sim._segment

    def charged_segment(z, x, xhat, res, t, mask, keys):
        n, k, r_true = 8, 7, 0.05
        comm = bool(np.asarray(mask)[0])
        per = 1.0 / n + (k * r_true if comm else 0.0)
        clock.t += per * len(np.asarray(mask))
        return real_segment(z, x, xhat, res, t, mask, keys)

    monkeypatch.setattr(sim, "_segment", charged_segment)
    import jax.numpy as jnp
    ctrl = DenseController(sched, warmup_comm=2)
    trace = sim.run_adaptive(jnp.zeros((8, 4)), T=200, eval_every=20,
                             seed=0, controller=ctrl, timer=clock)
    assert trace.iters[-1] == 200
    # constant injected timings -> the EW means are exact, and inverting
    # eq. 9 recovers the injected r exactly: t_msg = (t_comm - t_plain)/k
    # = r_true, t_full = n * t_plain = 1, r_hat = r_true
    assert ctrl.tracker.r_hat == pytest.approx(0.05, rel=1e-6)
    # ...for which eq. 21 says h_opt = sqrt(8*7*0.05/30) ~ 0.3 -> h stays 1
    assert sched.h_current == 1
    # and with a 100x costlier message the schedule must splice h upward
    sched2 = AdaptiveSchedule(h0=1)
    sim2 = DDASimulator(prob.subgrad_stack, prob.objective,
                        complete_graph(8), sched2,
                        a_fn=stepsize_sqrt(0.5), r=5.0)
    clock2 = FakeClock()
    real_segment2 = sim2._segment

    def charged_segment2(z, x, xhat, res, t, mask, keys):
        comm = bool(np.asarray(mask)[0])
        per = 1.0 / 8 + (7 * 5.0 if comm else 0.0)
        clock2.t += per * len(np.asarray(mask))
        return real_segment2(z, x, xhat, res, t, mask, keys)

    monkeypatch.setattr(sim2, "_segment", charged_segment2)
    ctrl2 = DenseController(sched2, warmup_comm=2)
    sim2.run_adaptive(jnp.zeros((8, 4)), T=200, eval_every=20, seed=0,
                      controller=ctrl2, timer=clock2)
    assert sched2.h_current > 1, "expensive comm must raise h"
    assert sched2.retunes, "a retune must be recorded"


def test_dense_adaptive_through_run_api():
    spec = ExperimentSpec(
        name="dense-adaptive",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": 8, "d": 4, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule={"kind": "adaptive", "params": {"h0": 1}},
        controller={"kind": "dense_adaptive",
                    "params": {"warmup_comm": 2, "warmup_plain": 1}},
        backends=[{"kind": "dense"}],
        stepsize={"kind": "sqrt", "params": {"A": 0.5}},
        T=120, eval_every=20, seed=0, r=0.5)
    res = run(spec)
    assert res.trace.iters[-1] == 120
    assert "retunes" in res.extras and "r_hat" in res.extras
    # no phantom end-of-run splice: every recorded retune shaped at least
    # one future iteration
    assert all(t < 120 for t, _ in res.extras["retunes"])
    assert np.isfinite(res.trace.fvals).all()


# ---------------------------------------------------------------------------
# reweight_gossip (StragglerReweighter acting on the real mix)
# ---------------------------------------------------------------------------


def _reweight_spec(engine):
    return ExperimentSpec(
        name="reweight",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": 8, "d": 4, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 8, "seed": 0}},
        schedule={"kind": "adaptive", "params": {"h0": 1}},
        controller={"kind": "adaptive",
                    "params": {"update_every": 0.5, "warmup_messages": 4,
                               "warmup_steps": 4, "reweight_gossip": True}},
        backends=[{"kind": "netsim",
                   "params": {"scenario": "straggler", "slow_factor": 4.0,
                              "n_slow": 2, "engine": engine}}],
        stepsize={"kind": "inv_sqrt", "params": {"A": 0.5}},
        T=500, eval_every=10, seed=0, r=0.5, eps_frac=0.05,
        time_limit=3000.0)


@pytest.mark.parametrize("engine", ["object", "vectorized"])
def test_reweight_gossip_converges(engine):
    """Convergence smoke: with the reweighted P driving the ACTUAL gossip
    on a straggler-heavy cluster, the run still reaches the 5% target (the
    reweighted rows stay convex combinations, so DDA's contraction
    survives), and the flag actually engaged."""
    res = run(_reweight_spec(engine))
    assert res.extras["reweight_gossip"] is True
    assert res.time_to_target is not None, "never reached the 5% target"
    assert res.extras["lam2_eff"] is not None
    prob = problems.build("quadratic_consensus", n=8, d=4, seed=0)
    gap0 = prob.f0() - prob.fstar
    assert res.trace.fvals[-1] - prob.fstar < 0.1 * gap0


def test_reweight_gossip_rejected_for_pushsum():
    spec = _reweight_spec("vectorized")
    bad = spec.with_value("backends.0.params.algorithm", "pushsum")
    with pytest.raises(ValueError, match="stale-gossip"):
        run(bad)


def test_mix_weights_off_keeps_uniform_path():
    """reweight_gossip=False (default) must leave Network.mix_weights None
    for the whole run -- the bit-identity contract's precondition."""
    from repro.netsim import NetSimulator
    spec = _reweight_spec("vectorized")
    no_rw = ExperimentSpec.from_dict({
        **spec.to_dict(),
        "controller": {"kind": "adaptive",
                       "params": {"update_every": 0.5,
                                  "warmup_messages": 4,
                                  "warmup_steps": 4}}})
    a = run(no_rw)
    assert a.extras["reweight_gossip"] is False
    b = run(ExperimentSpec.from_dict(no_rw.to_dict()))
    for f in TRACE_FIELDS:
        assert getattr(a.trace, f) == getattr(b.trace, f)
