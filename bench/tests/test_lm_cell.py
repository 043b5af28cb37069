"""The `launch.consensus4` cell's files at a small size on the CPU: the
plain DeepSeek-V2-Lite (`bench/problems/lm.py`) judges the program's
training runs through the harness, and the cell's per-layer readers read
the program's `lm.*` scopes.

The cell's root here holds the cell's configuration file with a registry
config's small widths (`data/lite_sizes.py`, DeepSeek-V2-Lite's
`smoke_ep2`: 4 of 8 experts held), its traffic mix and its problem
module, as files; the runs go on four host devices in a subprocess.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench import harness, layers, lm_layers, trace

ROOT = harness.ROOT
BENCH = ROOT / "bench"
CELL = "launch.consensus4"
SEED = 2 ** 33 + 19
#: the small model's sound runs read 1.5e-4 to 5.2e-4 here (bfloat16
#: against float32, lr 1e-2 over T=8); the fp8 control 1.3e-3 to 1.5e-3
LIMITS = {"loss_rel_gap": 1e-3, "trace_layout_mismatch": 0}

SCRIPT = """
import json, pathlib, sys, time
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from bench import control, harness

cell = harness.load_cell(sys.argv[3], pathlib.Path(sys.argv[1]))
device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
out = harness.run_cell(cell, int(sys.argv[4]), 0.1, False,
                       time.perf_counter(), device)
print(json.dumps({"result": out}), flush=True)
control.read(cell, [int(sys.argv[4])],
             lambda text: print(json.dumps({"control": json.loads(text)}),
                                flush=True))
"""


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.models import registry
    sizes = harness.load_module(ROOT, "tests", "data/lite_sizes")
    small = registry.get_config("deepseek-v2-lite", "smoke_ep2")
    cfg = sizes.bench_cfg(small, batch=2, seq=64)
    cfg["backend"] = {"kind": "launch",
                      "params": {"mesh": [4, 1, 1], "lr": 1e-2}}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    for group in ("problems", "configs", "traffic", "limits"):
        (tmp / "bench" / group).mkdir(parents=True, exist_ok=True)
    shutil.copy(BENCH / "problems" / "lm.py",
                tmp / "bench" / "problems" / "lm.py")
    shutil.copy(BENCH / "traffic" / f"{cell['traffic']}.json",
                tmp / "bench" / "traffic" / f"{cell['traffic']}.json")
    (tmp / config["file"]).write_text(json.dumps(cfg))
    (tmp / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps(LIMITS))
    (tmp / "BENCHMARK.json").write_text(json.dumps(
        dict(bench, configs=[config], workloads=[cell], per_layer=[])))
    return tmp


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lite_root")
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
    result = next(x["result"] for x in lines if "result" in x)
    control = [x["control"] for x in lines if "control" in x]
    return result, control


def test_the_cell_is_correct(runs):
    out, _ = runs
    assert out["correct"], out["checks"]
    assert out["failed"] == 0, out["errors"]
    assert out["checks"]["loss_rel_gap"]["value"] < LIMITS["loss_rel_gap"]
    assert out["checks"]["trace_layout_mismatch"]["value"] == 0


def test_the_fp8_control_reads_above_the_program(runs):
    _, (line, summary) = runs
    assert line["seed"] == SEED
    program = line["program"]["loss_rel_gap"]
    control = line["control"]["loss_rel_gap"]
    assert program < LIMITS["loss_rel_gap"] < control
    assert summary["upper"]["loss_rel_gap"] == control


# -- the readers of the lm scopes -------------------------------------------


@pytest.mark.parametrize("stack,scope", [
    ("jit(fused_step)/shard_map/jvp(lm.mla)/while/body/dot_general",
     "lm.mla"),
    ("jit(local_step)/shard_map/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/lm.moe/lm.moe.experts/convert",
     "lm.moe.experts"),
    ("jit(fused_step)/lm.gossip/pq,q...->p.../dot_general", "lm.gossip"),
    ("jit(local_step)/shard_map/lm.optimizer/mul", "lm.optimizer"),
    ("jit(dda_scan_allcomm)/while/body/dda.mix/gather", ""),
    ("", ""),
])
def test_scope_of_a_name_stack(stack, scope):
    assert lm_layers.scope_of(stack) == scope


def _context(tmp_path):
    """A context of one chip's ops, 10 s of window, busy 8 s: lm.mla 3 s,
    lm.moe.route 1 s, lm.moe.experts 2 s, lm.moe.shared 0.5 s, lm.gossip
    0.5 s, lm.optimizer 1 s; two solves of T=8 steps."""
    s = 1e9
    named = [("%a", "jit(local_step)/shard_map/jvp(lm.mla)/dot", 3),
             ("%b", "jit(local_step)/shard_map/lm.moe/lm.moe.route/top_k", 1),
             ("%c", "jit(local_step)/transpose(jvp())/lm.moe/lm.moe.experts/"
                    "ragged_dot", 2),
             ("%d", "jit(local_step)/lm.moe/lm.moe.shared/dot", 0.5),
             ("%e", "jit(fused_step)/lm.gossip/all-gather", 0.5),
             ("%f", "jit(local_step)/shard_map/lm.optimizer/mul", 1)]
    ops, stacks, t = [], {}, 0.0
    for name, stack, secs in named:
        ops.append(trace.Op(name, "f32[1]", "fusion", "", t, t + secs * s))
        stacks[(name, t)] = stack
        t += secs * s
    cell = harness.load_cell(CELL)
    window = harness.Window(seconds=10.0, solves=2, failed=0,
                            iterations=16, traces=[], errors=[])
    ctx = trace.Context([ops], [(0.0, 10 * s)], cell, window,
                        {"kind": "TPU v5 lite"})
    layers.attach(ctx, layers.Layers(stacks, []))
    return ctx


def test_the_share_readers(tmp_path):
    ctx = _context(tmp_path)
    read = {name: harness.load_module(ROOT, "metrics", name).read(ctx)
            for name in ("mla_pct", "moe_pct", "gossip_pct")}
    assert read == pytest.approx({"mla_pct": 37.5, "moe_pct": 43.75,
                                  "gossip_pct": 6.25})


def test_mfu_and_expert_roofline(tmp_path):
    ctx = _context(tmp_path)
    lm = ctx.problem_module
    cfg = ctx.cell.cfg
    mfu = harness.load_module(ROOT, "metrics", "mfu").read(ctx)
    assert mfu == pytest.approx(100 * 16 * lm.step_flops(cfg) * 4
                                / (10.0 * 4 * 197e12))
    roof = harness.load_module(ROOT, "metrics", "expert_roofline").read(ctx)
    assert roof == pytest.approx(100 * 16 * lm.step_flops(cfg, "experts")
                                 / 197e12 / 2.0)


def test_readers_read_nothing_without_lm_scopes(tmp_path):
    """A trace of the DDA's programs holds no lm scope: the readers give
    None, and the harness leaves the metrics out."""
    ctx = _context(tmp_path)
    layers.attach(ctx, layers.Layers(
        {k: "jit(dda)/dda.mix/x" for k in layers.of(ctx).stacks}, []))
    for name in ("mla_pct", "moe_pct", "gossip_pct", "expert_roofline"):
        assert harness.load_module(ROOT, "metrics", name).read(ctx) is None
