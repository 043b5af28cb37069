"""The launch backend's held programs: `ConsensusProgram` leased from the
serving layer's `CompileCache`, on four host devices in a subprocess (the
main test process keeps one device), with DeepSeek-V2-Lite's small
expert-parallel share (`smoke_ep2`: 4 of 8 experts held)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = """
import json
from repro.experiments import ExperimentSpec
from repro.serve import CompileCache, execute_requests

def spec(seed):
    return ExperimentSpec(
        name="held", seed=seed, T=4, eval_every=2,
        problem={"kind": "lm", "params": {
            "arch": "deepseek-v2-lite", "variant": "smoke_ep2",
            "batch_per_node": 2, "seq_len": 32}},
        topology={"kind": "complete", "params": {}},
        schedule={"kind": "periodic", "params": {"h": 2}},
        backends=[{"kind": "launch",
                   "params": {"mesh": [4, 1, 1], "lr": 1e-2}}])

def run(seed, cache):
    (r,), _ = execute_requests([spec(seed)], [None], cache)
    return {"seed": seed, "losses": r.trace.fvals,
            "compiled": r.extras["programs_compiled"],
            "dropped": r.extras["dropped_tokens"],
            "expert_tokens": r.extras["expert_tokens"],
            "counters": r.metrics.counters}

cache = CompileCache()
held = [run(seed, cache) for seed in (3, 5, 3)]
fresh = run(5, CompileCache())
print(json.dumps({"held": held, "fresh": fresh, "stats": cache.stats()}))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = f"{REPO / 'src'}:{REPO}"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_second_request_compiles_nothing(runs):
    first, second, again = runs["held"]
    assert first["compiled"] == 3          # init, local and fused steps
    assert second["compiled"] == 0 and again["compiled"] == 0
    assert runs["stats"]["misses"] == 1 and runs["stats"]["hits"] == 2


def test_held_programs_give_a_fresh_runs_losses(runs):
    """Weights and token streams come from each request's own seed: the
    held program's run of seed 5 is a fresh program's, and seed 3 run
    again is seed 3."""
    first, second, again = runs["held"]
    assert second["losses"] == runs["fresh"]["losses"]
    assert again["losses"] == first["losses"]
    assert first["losses"] != second["losses"]


def test_counters_come_back_with_the_result(runs):
    """Assignments per held expert, per MoE layer, summed over replicas
    and steps: 2 layers x 4 held experts; none dropped."""
    for r in runs["held"] + [runs["fresh"]]:
        assert r["dropped"] == 0
        routed = r["expert_tokens"]
        assert len(routed) == 2 and all(len(row) == 4 for row in routed)
        # each layer: 4 replicas x 4 steps x 2 x 32 tokens x top-3, about
        # half of them to the 4 held experts of 8
        total = 4 * 4 * 2 * 32 * 3
        assert all(0.25 * total < sum(row) < 0.75 * total for row in routed)
        assert r["counters"]["dropped_tokens"] == 0
        assert r["counters"]["expert_tokens"] == sum(map(sum, routed))


def test_launch_refuses_compression():
    """A compressor on the launch backend is refused, not dropped."""
    from repro.experiments import ExperimentSpec, run
    spec = ExperimentSpec(
        name="compressed", T=2, eval_every=1,
        problem={"kind": "lm", "params": {
            "arch": "llama3-8b", "variant": "smoke",
            "batch_per_node": 2, "seq_len": 16}},
        topology={"kind": "complete", "params": {}},
        schedule={"kind": "every"},
        compression={"kind": "topk", "params": {"keep": 0.5}},
        backends=[{"kind": "launch", "params": {"mesh": [1, 1, 1]}}])
    with pytest.raises(ValueError, match="compression"):
        run(spec)


def test_registry_refuses_an_unknown_variant():
    from repro.models import registry
    assert registry.get_config("deepseek-v2-lite", "ep8").moe_experts == 8
    with pytest.raises(ValueError, match="no variant"):
        registry.get_config("deepseek-v2-lite", "ep16")
