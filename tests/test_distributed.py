"""Multi-device distribution tests. These need >1 host device, so they run
in a SUBPROCESS with XLA_FLAGS set (the main test process keeps the default
single device per the dry-run contract)."""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = f"{REPO / 'src'}:{REPO}"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mix_collective_matches_dense_oracle():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import graphs as G, consensus as C
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("pod",))
        for name in ("complete", "ring", "hypercube", "expander4"):
            g = G.build_graph(name, 8)
            z = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)),
                            jnp.float32)
            def mix(zl):
                return C.mix_collective(zl[0], g, "pod")[None]
            f = jax.shard_map(mix, mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod"), axis_names={"pod"},
                              check_vma=False)
            got = jax.jit(f)(z)
            want = C.mix_dense(z, g.mixing_matrix())
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, err_msg=name)
        print("OK")
    """)


def test_consensus_sgd_equals_allreduce_dp():
    """Gossip parameter averaging (complete graph, h=1, plain SGD) must
    follow the EXACT same trajectory as synchronous all-reduce data
    parallelism -- the correctness anchor tying the paper's technique to
    standard DP."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import graphs as G, consensus as C

        n, d, steps, lr = 4, 6, 10, 0.1
        rng = np.random.default_rng(0)
        A = jnp.asarray(rng.normal(size=(n, 32, d)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(n, 32)), jnp.float32)

        def node_grad(w, Ai, bi):
            return jax.grad(lambda w_: jnp.mean(
                (Ai @ w_ - bi) ** 2))(w)

        # all-reduce DP: one shared w, mean gradient
        w_dp = jnp.zeros(d)
        for _ in range(steps):
            g = jnp.mean(jax.vmap(node_grad, (None, 0, 0))(w_dp, A, b), 0)
            w_dp = w_dp - lr * g

        # gossip DP: per-node w, local step then complete-graph average
        gC = G.complete_graph(n)
        w = jnp.zeros((n, d))
        for _ in range(steps):
            g = jax.vmap(node_grad)(w, A, b)
            w = w - lr * g
            w = C.mix_dense(w, gC.mixing_matrix())
        np.testing.assert_allclose(np.asarray(w[0]), np.asarray(w_dp),
                                   atol=1e-5)
        print("OK")
    """)


def test_consensus_steps_compile_and_converge():
    """make_consensus_steps on a (2,2,2) mesh: fused/local/mix all compile;
    loss decreases over 12 steps; per-pod losses stay close after mixing."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.launch.train import train_consensus_lm
        from repro.models import registry
        from repro.optim import adamw, constant_lr
        from repro.core.schedules import Periodic

        cfg = registry.get_config("llama3-8b", "smoke")
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rep = train_consensus_lm(cfg, adamw(constant_lr(2e-3)), mesh,
                                 steps=12, schedule=Periodic(h=3),
                                 topology="complete", batch_per_node=2,
                                 log_every=0)
        assert rep.losses[-1] < rep.losses[0], rep.losses
        print("OK")
    """)


def test_dryrun_single_cell_subprocess():
    """The dry-run itself (512 placeholder devices) for one small cell."""
    _run("""
        import subprocess, sys
        # run the real dryrun module (it sets its own XLA_FLAGS first)
        import os
        os.environ.pop("XLA_FLAGS", None)
        from importlib import reload
        import repro.launch.dryrun  # noqa: F401  (sets 512 devices)
        import jax
        assert jax.device_count() == 512, jax.device_count()
        from repro.configs.shapes import ShapeCell
        from repro.launch.dryrun import dryrun_cell
        cell = ShapeCell("train_4k", 4096, 256, "train")
        rec = dryrun_cell("musicgen-medium", cell, False, save=False,
                          verbose=False)
        assert rec["cost"].get("flops", 0) > 0
        assert rec["memory"]["temp_size_in_bytes"] > 0
        print("OK")
    """)
