"""repro.obs: tracer, metrics registry, exporters, and their integration
with every backend `repro.run()` dispatches to.

The load-bearing contracts:

  * every backend returns a populated `RunResult.metrics` whose
    compile_s + execute_s equals wall_s exactly (the JSON back-compat
    invariant: wall_s stays the lump sum);
  * `RunMetrics` round-trips exactly through the strict-RFC JSON path;
  * detail tracing is observational -- traced runs are bit-identical to
    untraced ones (the engines' single-branch hook contract);
  * the Chrome-trace exporter never mixes the host and sim clocks in one
    Perfetto process;
  * checked-in BENCH_*.json files carry the full warm-run sample arrays
    and schema-valid metrics blocks.
"""

import json
import math
import pathlib

import numpy as np
import pytest

from repro.experiments import ExperimentSpec, run
from repro.experiments.result import RunResult
from repro.obs import (METRICS_VERSION, RunMetrics, Tracer,
                       chrome_trace_events, profile_ctx, render_summary,
                       sample_quantiles, write_chrome_trace,
                       write_json_artifact, write_jsonl)

REPO = pathlib.Path(__file__).resolve().parents[1]


def _tiny_spec(**overrides):
    """Small, fast quadratic-consensus spec shared by the backend tests."""
    base = dict(
        name="obs_tiny",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": 6, "d": 4, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule={"kind": "every"},
        backends=[{"kind": "dense"}],
        T=40, eval_every=10, seed=0, r=0.05)
    base.update(overrides)
    return ExperimentSpec(**base)


# -- tracer ------------------------------------------------------------------


def test_tracer_spans_counters_series_and_phase_totals():
    tr = Tracer()
    with tr.span("build"):
        pass
    tr.add_host_span("execute", 1.0, 2.0)
    tr.add_host_span("execute", 3.0, 0.5)
    tr.add_span("step", 0.0, 0.125, track="node0")        # sim clock
    tr.add_instant("retune", 5.0, track="controller")
    tr.count("msgs", 10)
    tr.count("msgs", 5)
    tr.record_series("r_hat", 1.0, 0.05)
    totals = tr.phase_totals()
    assert totals["execute"] == {"total_s": 2.5, "count": 2}
    assert "step" not in totals          # sim-clock events are not phases
    assert "retune" not in totals        # instants are not phases
    assert tr.counters["msgs"] == 15
    assert tr.series["r_hat"] == [(1.0, 0.05)]


def test_tracer_caps_events_and_counts_drops():
    tr = Tracer(max_events=3)
    for i in range(10):
        tr.add_span("step", float(i), 1.0)
    assert len(tr.events) == 3
    assert tr.events_dropped == 7
    tr.count("c", 1.0)  # counters are never dropped
    assert tr.counters["c"] == 1.0


def test_tracer_batch_spans_match_singles():
    a, b = Tracer(), Tracer()
    t0s, durs = [0.0, 1.0, 2.5], [0.5, 0.25, 1.0]
    a.add_spans("step", t0s, durs, tracks=["n0", "n1", "n2"])
    for t0, dur, trk in zip(t0s, durs, ["n0", "n1", "n2"]):
        b.add_span("step", t0, dur, track=trk)
    assert [(e.name, e.t0, e.dur, e.track) for e in a.events] \
        == [(e.name, e.t0, e.dur, e.track) for e in b.events]


# -- metrics -----------------------------------------------------------------


def test_sample_quantiles_shape_and_empty():
    q = sample_quantiles([1.0, 2.0, 3.0, 4.0], "host")
    assert q["n"] == 4 and q["unit"] == "host"
    assert q["p50"] == pytest.approx(2.5)
    assert q["max"] == 4.0
    assert sample_quantiles([], "sim") is None


def test_runmetrics_round_trips_exactly():
    m = RunMetrics(
        compile_s=0.5, execute_s=1.25, eval_s=0.01, msgs=120,
        bytes_on_wire=3e4, drops=7, gossip_rounds=40, retunes=1,
        retune_history=[(3.0, 2, 1.362, 0.05, 0.4)], r_hat=0.05,
        r_hat_trajectory=[(1.0, 0.04), (2.0, 0.05)],
        step_time_quantiles={"p50": 0.1, "p90": 0.2, "p99": 0.3,
                             "max": 0.4, "n": 10, "unit": "sim"},
        phases={"execute": {"total_s": 1.25, "count": 1}},
        counters={"msgs": 120.0})
    d = m.to_dict()
    assert d["metrics_version"] == METRICS_VERSION
    # the dict must be strict-RFC serializable and loadable
    m2 = RunMetrics.from_dict(json.loads(json.dumps(d, allow_nan=False)))
    assert m2 == m


def test_runmetrics_rejects_bad_version_and_unknown_fields():
    d = RunMetrics().to_dict()
    bad = dict(d, metrics_version=99)
    with pytest.raises(ValueError, match="metrics_version"):
        RunMetrics.from_dict(bad)
    bad = dict(d, not_a_field=1)
    with pytest.raises(ValueError, match="unknown"):
        RunMetrics.from_dict(bad)


def test_runmetrics_from_tracer_inherits_aggregates():
    tr = Tracer()
    with tr.span("build"):
        pass
    tr.count("msgs", 3)
    tr.record_series("r_hat", 2.0, 0.1)
    m = RunMetrics.from_tracer(tr, execute_s=1.0)
    assert "build" in m.phases
    assert m.counters["msgs"] == 3
    assert m.r_hat_trajectory == ((2.0, 0.1),)


# -- exporters ---------------------------------------------------------------


def _traced_tracer():
    tr = Tracer(detail=True)
    with tr.span("execute"):
        pass
    tr.add_span("flight", 1.0, 0.05, track="net", src=0, dst=1)
    tr.add_instant("drop", 2.0, track="net")
    tr.count("msgs", 4)
    tr.record_series("r_hat", 1.0, 0.05)
    return tr


def test_chrome_trace_keeps_clocks_in_separate_pids(tmp_path):
    tr = _traced_tracer()
    events = chrome_trace_events(tr, run_name="t")
    host = [e for e in events if e.get("ph") == "X" and e["pid"] == 1]
    sim = [e for e in events if e.get("ph") in "Xi" and e["pid"] == 2]
    assert host and sim
    assert {e["name"] for e in host} == {"execute"}
    assert {e["name"] for e in sim} == {"flight", "drop"}
    # counters land as terminal "C" samples
    assert any(e["ph"] == "C" and e["name"] == "msgs" for e in events)
    path = write_chrome_trace(tr, tmp_path / "t.trace.json", run_name="t")
    payload = json.loads(pathlib.Path(path).read_text())
    assert payload["traceEvents"] == json.loads(json.dumps(events))
    assert payload["otherData"]["series"]["r_hat"] == [[1.0, 0.05]]


def test_jsonl_export_round_trips_the_event_stream(tmp_path):
    tr = _traced_tracer()
    path = write_jsonl(tr, tmp_path / "t.trace.jsonl")
    recs = [json.loads(line)
            for line in pathlib.Path(path).read_text().splitlines()]
    kinds = [r["kind"] for r in recs]
    assert kinds.count("span") == 2 and kinds.count("instant") == 1
    assert {"counter", "series"} <= set(kinds)
    flight = next(r for r in recs if r["name"] == "flight")
    assert flight["clock"] == "sim" and flight["args"] == {"src": 0, "dst": 1}


def test_write_json_artifact_sanitizes_nonfinite(tmp_path):
    path = write_json_artifact(tmp_path / "sub" / "a.json",
                               {"x": math.inf, "y": np.float64(2.0)})
    loaded = json.loads(pathlib.Path(path).read_text())
    assert loaded == {"x": None, "y": 2.0}


# -- backend integration -----------------------------------------------------


def test_dense_run_populates_metrics_and_wall_split():
    res = run(_tiny_spec())
    m = res.metrics
    assert m is not None
    assert m.compile_s + m.execute_s == pytest.approx(res.wall_s, abs=1e-12)
    assert m.gossip_rounds == 40           # every-iteration schedule
    assert m.msgs == 40 * 6 * 4            # rounds * n * k
    assert m.bytes_on_wire == m.msgs * 4 * 4.0
    assert {"build"} <= set(m.phases)
    # the whole run is one program call, and one blocked wait for it
    assert m.phases["wait"]["count"] == 1
    assert m.phases["wait"]["total_s"] <= m.execute_s


@pytest.mark.parametrize("engine", ["object", "vectorized"])
def test_netsim_run_populates_metrics(engine):
    spec = _tiny_spec(backends=[{"kind": "netsim",
                                 "params": {"scenario": "lossy",
                                            "loss": 0.2,
                                            "engine": engine}}])
    res = run(spec)
    m = res.metrics
    assert m.compile_s == 0.0
    assert m.execute_s == pytest.approx(res.wall_s, abs=1e-12)
    assert m.msgs == res.extras["sent"] > 0
    assert m.drops == res.extras["drops"] > 0
    assert m.bytes_on_wire > 0
    assert m.step_time_quantiles["unit"] == "sim"
    assert m.step_time_quantiles["n"] == 6 * 40


def test_netsim_detail_tracing_is_bit_identical_and_populates_events():
    spec = _tiny_spec(backends=[{"kind": "netsim",
                                 "params": {"scenario": "lossy",
                                            "loss": 0.2}}])
    plain = run(spec)
    tr = Tracer(detail=True)
    traced = run(spec, tracer=tr)
    for field in ("iters", "sim_time", "fvals", "fvals_consensus",
                  "comms", "disagreement"):
        assert getattr(plain.trace, field) == getattr(traced.trace, field)
    names = {e.name for e in tr.events if e.clock == "sim"}
    assert {"step", "flight", "drop", "eval"} <= names


@pytest.mark.parametrize("engine", ["object", "vectorized"])
def test_netsim_detail_timeline_mirrors_observability_lists(engine):
    """Each engine's emitted detail timeline must describe exactly the
    events its (bit-identity-regression-tested) observability lists
    record: every kept flight becomes one span, every local step one span,
    every drop is accounted (the vectorized engine batches drops into
    per-ship instants carrying a count)."""
    from repro.netsim import NetSimulator
    from repro.netsim.scenarios import lossy
    from repro.netsim.problems import quadratic_consensus

    n, d, T = 6, 4, 40
    _centers, grad_fn, eval_fn = quadratic_consensus(n, d, seed=0)
    tr = Tracer(detail=True)
    sim = NetSimulator(lossy(n, 0.05, loss=0.2), grad_fn, eval_fn,
                       seed=0, engine=engine, tracer=tr)
    sim.run(np.zeros((n, d)), T, eval_every=10)

    flights = sorted(e.dur for e in tr.events if e.name == "flight")
    assert flights == sorted(sim.msg_flights)
    steps = sorted(e.dur for e in tr.events if e.name == "step")
    assert steps == pytest.approx(sorted(sim.compute_times))
    drop_events = [e for e in tr.events if e.name == "drop"]
    dropped = sum(e.args.get("count", 1) for e in drop_events)
    assert dropped == sim.drops > 0


def test_adaptive_netsim_metrics_carry_retunes_and_trajectory():
    spec = ExperimentSpec.from_file(
        REPO / "benchmarks" / "manifests" / "adaptive_adversarial.json")
    res = run(spec)  # first declared backend: the adversarial netsim cell
    m = res.metrics
    assert m.retunes == len(m.retune_history) == len(res.extras["retunes"])
    assert m.r_hat == res.extras["r_hat"]
    assert len(m.r_hat_trajectory) > 0
    # trajectory times are on the sim clock, monotonically nondecreasing
    ts = [t for t, _ in m.r_hat_trajectory]
    assert ts == sorted(ts)
    assert "rtracker.messages_observed" in m.counters


def test_launch_dryrun_populates_metrics():
    spec = ExperimentSpec.from_file(
        REPO / "benchmarks" / "manifests" / "launch_dryrun.json")
    res = run(spec)
    m = res.metrics
    assert m.compile_s > 0.0               # the AOT compile walls
    assert m.compile_s + m.execute_s == pytest.approx(res.wall_s, abs=1e-9)
    assert m.msgs == 0                     # dryrun runs zero steps
    assert any(p.startswith("compile:") for p in m.phases)


def test_result_json_round_trips_metrics():
    res = run(_tiny_spec())
    d = json.loads(res.to_json())
    res2 = RunResult.from_dict(d)
    assert res2.metrics == res.metrics
    # pre-metrics artifacts stay loadable (back-compat)
    d.pop("metrics")
    assert RunResult.from_dict(d).metrics is None


def test_render_summary_shows_phases_and_counters():
    res = run(_tiny_spec())
    text = render_summary(json.loads(res.to_json()))
    assert "backend=dense" in text
    assert "compile" in text and "execute" in text
    assert "msgs" in text
    assert "r̂ vs r:" in text


def test_render_summary_premetrics_artifact():
    text = render_summary({"spec": {"name": "old"},
                           "backend": {"kind": "dense"}, "wall_s": 1.0})
    assert "predates repro.obs" in text


# -- profiling hook ----------------------------------------------------------


def test_profile_ctx_none_is_noop():
    with profile_ctx(None):
        pass


def test_profile_dir_rejected_off_dense(tmp_path):
    spec = _tiny_spec(profile_dir=str(tmp_path),
                      backends=[{"kind": "netsim"}])
    with pytest.raises(ValueError, match="profile_dir"):
        run(spec)


def test_dense_profile_dir_captures_a_device_trace(tmp_path):
    run(_tiny_spec(profile_dir=str(tmp_path)))
    files = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert files, "jax.profiler produced no trace files"


def test_dense_run_spans_reach_the_profiler(tmp_path):
    """A dense run under `jax.profiler` leaves its host spans on the
    trace's host plane as `repro.*` annotations, on the device ops' clock:
    build, the dispatch of the run's arguments, compile, then per program
    call an execute holding its dispatch and wait, then assemble."""
    import jax

    spec = _tiny_spec()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.solve"):
            res = run(spec)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans: dict[str, list] = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("repro.", "test.")):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    for name in ("build", "compile", "execute", "dispatch", "wait",
                 "assemble"):
        assert len(spans[f"repro.{name}"]) == \
            res.metrics.phases[name]["count"], name

    def inside(child, parents):
        return any(a <= child[0] and child[1] <= b for a, b in parents)

    (solve,) = spans["test.solve"]
    (build,) = spans["repro.build"]
    (compile_,) = spans["repro.compile"]  # a fresh simulator compiles
    for name in ("build", "compile", "execute", "assemble"):
        assert all(inside(s, [solve]) for s in spans[f"repro.{name}"])
    first, *calls = sorted(spans["repro.dispatch"])
    for s in calls + spans["repro.wait"]:
        assert inside(s, spans["repro.execute"])
    assert build[1] <= first[0] and first[1] <= compile_[0]
    assert compile_[1] <= min(spans["repro.execute"])[0]
    assert max(spans["repro.execute"])[1] <= min(spans["repro.assemble"])[0]


# -- CLI ---------------------------------------------------------------------


def test_cli_run_writes_traces_and_trace_renders(tmp_path, capsys):
    from repro.experiments.__main__ import main

    manifest = tmp_path / "tiny.json"
    manifest.write_text(_tiny_spec().to_json())
    out = tmp_path / "out"
    assert main(["run", str(manifest), "--out", str(out)]) == 0
    result_path = out / "obs_tiny__dense.json"
    trace_path = out / "obs_tiny__dense.trace.json"
    jsonl_path = out / "obs_tiny__dense.trace.jsonl"
    assert result_path.exists() and trace_path.exists() and jsonl_path.exists()
    payload = json.loads(trace_path.read_text())
    assert payload["traceEvents"], "trace must carry events"
    assert {e["pid"] for e in payload["traceEvents"]} >= {1}
    capsys.readouterr()
    assert main(["trace", str(result_path)]) == 0
    text = capsys.readouterr().out
    assert "phases:" in text and "counters:" in text


def test_cli_trace_reports_unreadable_file(tmp_path, capsys):
    from repro.experiments.__main__ import main

    assert main(["trace", str(tmp_path / "missing.json")]) == 2
    assert "cannot read" in capsys.readouterr().out


# -- checked-in bench artifacts ----------------------------------------------


def _bench_paths():
    return sorted(REPO.glob("BENCH_*.json"))


def _assert_quantiles(q, n_samples):
    assert q["n"] == n_samples and q["unit"] == "host"
    assert q["p50"] <= q["p90"] <= q["p99"] <= q["max"]


@pytest.mark.parametrize("path", _bench_paths(), ids=lambda p: p.stem)
def test_checked_in_bench_files_are_schema_valid(path):
    """Every checked-in BENCH_*.json must be strict-RFC JSON carrying the
    full warm-run sample arrays and their quantiles: per result cell with
    a version-1 RunMetrics block (throughput benches), or per latency
    spec plus the equivalence/acceptance gates (the serve bench — schema
    in benchmarks/README.md)."""
    raw = path.read_text()
    assert "NaN" not in raw and "Infinity" not in raw
    report = json.loads(raw)
    for key in ("benchmark", "mode", "config", "host"):
        assert key in report, f"{path.name} missing {key!r}"

    if report["benchmark"] == "serve":
        assert report["equivalence"]["ok"] is True
        lat = report["latency"]
        warm_total = 0
        for cell in lat["per_spec"]:
            samples = cell["warm_samples_s"]
            assert samples and all(s >= 0 for s in samples)
            assert cell["cold_s"] >= 0
            warm_total += len(samples)
        _assert_quantiles(lat["cold_quantiles"], len(lat["per_spec"]))
        _assert_quantiles(lat["warm_quantiles"], warm_total)
        thr = report["throughput"]
        assert thr["specs_per_sec"] > 0
        assert 0.0 <= thr["lanes"]["occupancy"] <= 1.0
        assert 0.0 <= thr["cache"]["hit_rate"] <= 1.0
        acc = report["acceptance"]
        assert acc["pass"] and acc["measured"] >= 0
        return

    assert report["results"], f"{path.name} has no result cells"
    for cell in report["results"]:
        samples = cell["wall_samples_s"]
        assert samples and all(s >= 0 for s in samples)
        _assert_quantiles(cell["wall_quantiles"], len(samples))
        m = RunMetrics.from_dict(cell["metrics"])  # schema-validates
        assert m.compile_s >= 0.0 and m.execute_s >= 0.0
