"""CPU tests of training cells: a `launch` configuration driven and judged
by the harness against `bench/train_ref.py`.

A test root holds only files: a configuration, a traffic mix, a limits
file and `bench/problems/lm.py`, the stand-in plain model of
`bench/tests/data/lm_standin.py` (the program's transformer at a registry
smoke size, so that these tests check the training loop of the reference
on its own). The runs on four devices go in one subprocess with
`--xla_force_host_platform_device_count=4`, as the program's own
multi-device tests do.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import check, generator, harness, train_ref

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
STANDIN = BENCH / "tests" / "data" / "lm_standin.py"
SEED = 2 ** 33 + 5  # above 32 bits, as the benchmark's seeds are

LM = {
    "name": "lm_smoke",
    "vocab_size": 512,
    "problem": {"kind": "lm",
                "params": {"arch": "llama3-8b", "variant": "smoke",
                           "batch_per_node": 2, "seq_len": 32}},
    "topology": {"kind": "complete", "params": {}},
    "mixing": {"complete": True},
    "r": 0.05,
    "backend": {"kind": "launch", "params": {"mesh": [4, 1, 1], "lr": 1e-2}},
    "precision": "bfloat16", "matmul_precision": None,
    "control": {"dtype": "bfloat16", "matmul_precision": "default"},
}
TRAFFIC = {"name": "periodic_h2", "loop": "closed", "clients": 1,
           "schedule": {"kind": "periodic", "params": {"h": 2}},
           "T": 6, "eval_every": 2, "compression": None}
#: sound runs read loss_rel_gap 3.8e-4-4.4e-4 here (bfloat16 against the
#: float32 reference); the token stream shifted by one step 5.0e-3, the
#: gossip left out 0.23
LIMITS = {"loss_rel_gap": 1.5e-3, "trace_layout_mismatch": 0}
CELL = "lm.smoke.periodic_h2"


def make_root(tmp: pathlib.Path, cfg=LM, traffic=TRAFFIC) -> pathlib.Path:
    """A benchmark root holding one training cell, made only of files."""
    bench = tmp / "bench"
    for group in ("problems", "configs", "traffic", "limits"):
        (bench / group).mkdir(parents=True, exist_ok=True)
    shutil.copy(STANDIN, bench / "problems" / "lm.py")
    (bench / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (bench / "traffic" / f"{traffic['name']}.json").write_text(
        json.dumps(traffic))
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    bench_json = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": cfg["name"], "source": "registry smoke size",
                     "file": f"bench/configs/{cfg['name']}.json",
                     "reduced": [], "why": "stand-in"}],
        "workloads": [{"name": CELL, "config": cfg["name"],
                       "traffic": traffic["name"], "chips": 1,
                       "why": "stand-in"}],
        "end_to_end": [
            {"name": "iters_per_s", "unit": "iter/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": []}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench_json))
    return tmp


#: runs the cell through `harness.run_cell` as it is, with the program's
#: gossip left out, and with the reference's tokens one step ahead; then
#: reads it with `bench/control.py`. One JSON line each.
SCRIPT = """
import json, pathlib, sys, time
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
import repro.launch.train as program
from bench import control, harness, train_ref

cell = harness.load_cell(sys.argv[3], pathlib.Path(sys.argv[1]))
seed = int(sys.argv[4])
device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
steps, tokens = program.make_consensus_steps, train_ref.batch_tokens

def no_gossip(*a, **k):
    local, mix, fused = steps(*a, **k)
    return local, mix, local

def shifted(seed, replica, step, *a):
    return tokens(seed, replica, step + 1, *a)

for fault in ("none", "no_gossip", "tokens_shifted"):
    program.make_consensus_steps = no_gossip if fault == "no_gossip" \\
        else steps
    train_ref.batch_tokens = shifted if fault == "tokens_shifted" \\
        else tokens
    out = harness.run_cell(cell, seed, 0.1, False, time.perf_counter(),
                           device)
    print(json.dumps({"fault": fault, "result": out}), flush=True)
program.make_consensus_steps, train_ref.batch_tokens = steps, tokens
control.read(cell, [seed],
             lambda text: print(json.dumps({"control": json.loads(text)}),
                                flush=True))
"""


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("train_root")
    root = make_root(tmp / "root")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}:{ROOT}", JAX_PLATFORMS="cpu",
               TF_CPP_MIN_LOG_LEVEL="3")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SCRIPT), str(root),
         str(tmp / "jax_cache"), CELL, str(SEED)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    runs = {x["fault"]: x["result"] for x in lines if "fault" in x}
    control = [x["control"] for x in lines if "control" in x]
    return runs, control


def test_launch_cell_of_files_is_correct(four_devices):
    runs, _ = four_devices
    out = runs["none"]
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0, out["errors"]
    assert set(out["checks"]) == set(LIMITS)
    assert out["checks"]["loss_rel_gap"]["value"] < 1e-3
    assert list(out["metrics"]) == ["iters_per_s", "setup_s"]
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["no_gossip", "tokens_shifted"])
def test_launch_cell_fault_is_not_correct(four_devices, fault):
    runs, _ = four_devices
    out = runs[fault]
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["loss_rel_gap"]["value"] > 3 * LIMITS["loss_rel_gap"]


def test_control_reads_a_training_cell(four_devices):
    """For each seed the control gives the program's numbers and the
    control's against the reference, then the summary."""
    _, lines = four_devices
    line, summary = lines
    assert line["seed"] == SEED
    assert set(line["program"]) == set(train_ref.NUMBERS)
    assert line["program"]["loss_rel_gap"] < LIMITS["loss_rel_gap"]
    assert set(line["control"]) == {"loss_rel_gap"}
    assert 0 < line["control"]["loss_rel_gap"] < 1
    assert "order" not in line
    assert summary["lower"] == line["program"]
    assert summary["upper"] == line["control"]


def test_lm_request_runs_through_repro():
    """The generator's request of an `lm` configuration is a spec that the
    program's `repro.run` accepts: no seed in the problem's params, the
    default stepsize."""
    import repro
    from repro.experiments import ExperimentSpec
    cfg = dict(LM, backend={"kind": "launch",
                            "params": {"mesh": [1, 1, 1], "lr": 1e-2}})
    traffic = dict(TRAFFIC, T=2, eval_every=1)
    kind = check.hooks(train_ref)
    request = generator.solve_request(cfg, traffic, SEED, 0,
                                      kind.spec_problem)
    assert "seed" not in request["problem"]["params"]
    assert "stepsize" not in request
    assert request["seed"] == SEED % generator.SEED_MODULUS
    result = repro.run(ExperimentSpec(**request))
    assert result.trace.iters == [1, 2]
    assert all(np.isfinite(result.trace.fvals))


# -- the reference's parts against the program's -----------------------------


def test_token_stream_is_the_programs():
    from repro.data.pipeline import TokenStream
    for seed, node, step in [(0, 0, 0), (7, 3, 5), (SEED % 2 ** 31, 1, 2)]:
        stream = TokenStream(512, 32, 2, node_index=node, num_nodes=4,
                             seed=seed)
        stream.close()
        assert np.array_equal(train_ref.batch_tokens(seed, node, step, 512,
                                                     2, 32),
                              stream._batch_at(step))


@pytest.mark.parametrize("h", [1, 2, 3, 5])
def test_schedules_are_the_programs(h):
    from repro.core.schedules import EveryIteration, Periodic
    for t in range(1, 40):
        assert train_ref.communicates({"kind": "periodic",
                                       "params": {"h": h}}, t) \
            == Periodic(h=h).is_comm_step(t)
        assert train_ref.communicates({"kind": "every"}, t) \
            == EveryIteration().is_comm_step(t)


def test_adamw_and_learning_rate_are_the_programs():
    """Three steps of the written-out AdamW on a tree of a bfloat16 and a
    float32 leaf give the program's optimizer's parameters, bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.optim import adamw, cosine_lr
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.bfloat16),
              "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    opt = adamw(cosine_lr(0.01, 5))
    state = opt.init(params)
    mine = params
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = m
    for t in range(1, 4):
        grads = jax.tree.map(
            lambda a: jnp.asarray(rng.normal(size=a.shape), jnp.float32),
            params)
        params, state = opt.update(grads, state, params)
        tt = jnp.asarray(t, jnp.int32)
        lr = train_ref.cosine_lr(0.01, 5, tt)
        assert lr == cosine_lr(0.01, 5)(tt)
        mine, m, v = train_ref.adamw(mine, grads, m, v, tt, lr)
        for k in params:
            assert mine[k].dtype == params[k].dtype
            assert np.array_equal(np.asarray(mine[k], np.float32),
                                  np.asarray(params[k], np.float32)), (t, k)


@pytest.mark.parametrize("topology,mixing", [
    ("complete", {"complete": True}),
    ("ring", {"shifts": [1, 3], "self_weight": 1 / 3, "edge_weight": 1 / 3}),
])
def test_mixing_is_the_programs_graph(topology, mixing):
    from repro.experiments.components import build_component, topologies
    graph = build_component(topologies, topology, {}, n=4)
    cfg = dict(LM, mixing=mixing)
    np.testing.assert_allclose(train_ref.mixing(cfg), graph.mixing_matrix(),
                               rtol=0, atol=1e-15)


# -- the generator and the judge by kind ------------------------------------


@pytest.mark.parametrize("traffic", [
    dict(TRAFFIC, compression={"kind": "topk", "params": {"keep": 0.5}}),
    dict(TRAFFIC, schedule={"kind": "sparse", "params": {"p": 0.3}}),
], ids=["compression", "unmodelled_schedule"])
def test_generator_refuses_what_launch_would_not_run_as_the_reference(
        traffic):
    with pytest.raises(ValueError):
        generator.check_supported(LM, traffic)


def test_dda_mix_is_still_supported():
    nonsmooth = json.loads((BENCH / "configs" /
                            "nonsmooth_expander_n256_d4096.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "topk_final.json").read_text())
    generator.check_supported(nonsmooth, traffic)


def test_judge_takes_the_kinds_numbers():
    train = check.hooks(train_ref)
    assert train.numbers == train_ref.NUMBERS and not train.dda
    values = {"loss_rel_gap": 2e-3, "trace_layout_mismatch": 0.0}
    ok, checks = check.judge(values, LIMITS, train.numbers)
    assert not ok and checks["loss_rel_gap"] == {"value": 2e-3,
                                                 "limit": 1.5e-3}
    # a DDA number in a training cell's limits, and the other way round
    with pytest.raises(KeyError):
        check.judge(values, {"fbar_rel_gap": 1e-5}, train.numbers)
    with pytest.raises(KeyError):
        check.judge(values, LIMITS)


def test_a_module_without_hooks_runs_the_dda():
    module = harness.load_module(ROOT, "problems", "nonsmooth")
    kind = check.hooks(module)
    assert kind.dda and kind.numbers == check.NUMBERS
    cfg = json.loads((BENCH / "configs" /
                      "nonsmooth_expander_n256_d4096.json").read_text())
    assert kind.spec_problem(cfg, SEED)["params"]["seed"] == SEED


def test_a_module_with_some_hooks_is_refused():
    import types
    module = types.ModuleType("half")
    module.NUMBERS = ("loss_rel_gap",)
    with pytest.raises(AttributeError):
        check.hooks(module)
