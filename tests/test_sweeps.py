"""run_sweep parallel executors: vmapped dense batching and the process
pool, against the serial baseline."""

import numpy as np
import pytest

from repro.experiments import ExperimentSpec, run_sweep


def _dense_spec(**kw):
    base = dict(
        name="sweep",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": 8, "d": 12, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule={"kind": "periodic", "params": {"h": 2}},
        backends=[{"kind": "dense"}],
        stepsize={"kind": "sqrt", "params": {"A": 0.5}},
        T=60, eval_every=20, seed=0, r=0.01, eps_frac=0.05)
    base.update(kw)
    return ExperimentSpec(**base)


def _netsim_spec():
    return ExperimentSpec(
        name="sweep-net",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": 8, "d": 6, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule={"kind": "every"},
        backends=[{"kind": "netsim",
                   "params": {"scenario": "lossy", "loss": 0.2}}],
        stepsize={"kind": "inv_sqrt", "params": {"A": 0.5}},
        T=40, eval_every=10, seed=0, r=0.01)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)))


@pytest.mark.parametrize("axis,values", [
    ("seed", [0, 1, 2]),
    ("schedule.params.h", [1, 2, 5]),
    ("r", [0.0, 0.01, 0.1]),
], ids=["seed", "h", "r"])
def test_vmap_sweep_matches_serial(axis, values):
    spec = _dense_spec()
    serial = run_sweep(spec, axis, values)
    vmapped = run_sweep(spec, axis, values, parallel="vmap")
    assert all(r.extras.get("vmap_lanes") == len(values) for r in vmapped)
    for a, b in zip(serial, vmapped):
        assert a.spec == b.spec
        assert a.trace.iters == b.trace.iters
        assert a.trace.sim_time == b.trace.sim_time
        assert a.trace.comms == b.trace.comms
        assert _rel(a.trace.fvals, b.trace.fvals) < 1e-6
        assert a.predictions == b.predictions
        assert a.eps_value == pytest.approx(b.eps_value)


def test_vmap_sweep_falls_back_when_not_batchable():
    """Shape-changing axes (problem n) and non-dense backends fall back to
    the serial executor -- correctly, and LOUDLY: every fallback result
    carries the reason the pool did not pack (metrics.notes + extras), so
    "my sweep got slow" is diagnosable from the artifacts."""
    res = run_sweep(_dense_spec(), "problem.params.n", [4, 8],
                    parallel="vmap")
    assert [r.spec.problem.params["n"] for r in res] == [4, 8]
    assert all("vmap_lanes" not in r.extras for r in res)
    for r in res:
        reason = r.metrics.notes["vmap_fallback"]
        assert reason == r.extras["vmap_fallback"]
        # a shape-incompatible pool must say WHY: the cells differ
        # outside the batchable lane fields
        assert "lane fields" in reason and "2 distinct" in reason
    res = run_sweep(_netsim_spec(), "seed", [0, 1], parallel="vmap")
    assert all("vmap_lanes" not in r.extras for r in res)
    assert all("not dense" in r.metrics.notes["vmap_fallback"] for r in res)
    # the reason survives the JSON artifact round-trip
    import repro
    rt = repro.RunResult.from_json(res[0].to_json())
    assert rt.metrics.notes["vmap_fallback"] == \
        res[0].metrics.notes["vmap_fallback"]


def test_vmap_sweep_whole_schedule_axis():
    """Sweeping the schedule COMPONENT (kind change every -> sparse) still
    batches: the comm pattern is data to the scanned program."""
    spec = _dense_spec()
    values = [{"kind": "every"}, {"kind": "sparse", "params": {"p": 0.3}}]
    serial = run_sweep(spec, "schedule", values)
    vmapped = run_sweep(spec, "schedule", values, parallel="vmap")
    assert all(r.extras.get("vmap_lanes") == 2 for r in vmapped)
    for a, b in zip(serial, vmapped):
        assert a.trace.comms == b.trace.comms
        assert _rel(a.trace.fvals, b.trace.fvals) < 1e-6


def test_process_sweep_matches_serial_bitwise():
    """netsim cells across a spawn pool: pure + seeded, so the merged
    results are bit-identical to the serial executor."""
    spec = _netsim_spec()
    serial = run_sweep(spec, "seed", [0, 1])
    proc = run_sweep(spec, "seed", [0, 1], parallel="process", processes=2)
    for a, b in zip(serial, proc):
        assert a.spec == b.spec
        assert a.trace.fvals == b.trace.fvals
        assert a.trace.sim_time == b.trace.sim_time
        assert a.trace.disagreement == b.trace.disagreement
        assert a.r_measurement == b.r_measurement
        assert a.extras["sent"] == b.extras["sent"]


def test_process_sweep_rejects_device_cells():
    """Only netsim cells fan out to processes: a dense cell runs on the
    device, which belongs to this process."""
    with pytest.raises(ValueError, match="netsim cells only"):
        run_sweep(_dense_spec(), "seed", [0, 1], parallel="process",
                  processes=2)


def test_run_sweep_rejects_unknown_parallel():
    with pytest.raises(ValueError, match="parallel"):
        run_sweep(_dense_spec(), "seed", [0], parallel="threads")
