"""Training driver: consensus data-parallel LM training with the paper's
communication schedules, checkpoint/restart, and optional straggler
simulation. This is the host loop the examples use; on a real cluster each
pod's process group runs exactly this with the mesh spanning its slice.

The schedule decides per iteration whether to run the cheap `local_step`
(no cross-pod collective) or the `fused_step` (local + consensus mixing) --
the paper's 1/n vs 1/n + kr cost split is directly visible as two compiled
programs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.graphs import CommGraph, build_graph
from repro.core.schedules import CommSchedule, EveryIteration
from repro.data.pipeline import TokenStream
from repro.launch import specs as sp
from repro.launch.steps import make_consensus_steps, make_train_step
from repro.models import transformer
from repro.models.common import ModelConfig
from repro.optim import Optimizer
from repro.runtime import sharding as shrules

PyTree = Any


@dataclasses.dataclass
class TrainReport:
    steps: int
    losses: list
    comm_rounds: int
    sim_time_units: float
    resumed_from: int | None = None
    # backend-specific observability (dryrun compile stats, wall timings);
    # surfaced as RunResult.extras by the repro.experiments launch backend
    extras: dict = dataclasses.field(default_factory=dict)


def init_pod_state(cfg: ModelConfig, optimizer: Optimizer, mesh,
                   n_pods: int, seed: int):
    """Concrete pod-stacked (params, opt_state) -- one independently
    initialized replica per pod, sharded P('pod', ...) on `mesh` -- plus
    their shardings `(psh, ssh)`. Call under `shrules.use_rules`."""
    aparams, pspecs = sp.param_specs(cfg, mesh)
    astate, sspecs = sp.opt_state_specs(optimizer, aparams, pspecs)
    aparams, pspecs = sp.pod_stack(aparams, pspecs, n_pods)
    astate, sspecs = sp.pod_stack(astate, sspecs, n_pods)
    psh = sp.to_shardings(pspecs, mesh)
    ssh = sp.to_shardings(sspecs, mesh)

    def init_all(key):
        def one(k_):
            prm, _ = transformer.init(k_, cfg)
            st = optimizer.init(prm)
            return prm, st
        return jax.vmap(one)(jax.random.split(key, n_pods))

    params, opt_state = jax.jit(
        init_all, out_shardings=(psh, ssh))(jax.random.PRNGKey(seed))
    return params, opt_state, psh, ssh


def train_consensus_lm(cfg: ModelConfig, optimizer: Optimizer, mesh,
                       *, steps: int = 100,
                       schedule: CommSchedule | None = None,
                       topology: str = "complete",
                       graph: CommGraph | None = None,
                       r_estimate: float = 0.05,
                       batch_per_node: int = 8,
                       seq_len: int = 64,
                       ckpt_dir: str | None = None,
                       ckpt_every: int = 50,
                       seed: int = 0,
                       log_every: int = 10,
                       mix_target: str = "params",
                       dryrun: bool = False,
                       tracer=None) -> TrainReport:
    """Run consensus DP training of `cfg` on `mesh` (axes pod, data, model).

    Returns per-step losses plus the simulated time-unit accounting
    (1/n per iteration + k*r per communication round, paper eq. 9/19).

    `graph` overrides the `topology` name with a prebuilt CommGraph (the
    repro.experiments runner resolves topologies through its registry and
    hands the built graph in; n must equal the mesh's pod-axis size).
    `dryrun` lowers + compiles both step programs (cheap local, fused
    local+mix) and returns after ZERO training steps with the compile
    timings in `extras` -- the CI smoke mode for the launch backend.

    `tracer` (optional `repro.obs.Tracer`) receives host-clock spans per
    training step / compile; the per-step walls and comm flags are also
    returned in `extras["step_walls"]` / `extras["step_comm"]` so the
    experiments runner can quote step-time quantiles without a tracer.
    """
    schedule = schedule or EveryIteration()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_pods = axis_sizes.get("pod", 1)
    if graph is None:
        graph = build_graph(topology, n_pods)
    elif graph.n != n_pods:
        raise ValueError(f"graph has n={graph.n} but the mesh has "
                         f"{n_pods} pods")
    k = graph.degree

    local, mix, fused = make_consensus_steps(
        cfg, optimizer, graph, mesh,
        moe_groups=max(axis_sizes.get("data", 1), 1) if cfg.moe_experts else 1,
        mix_target=mix_target)

    with shrules.use_rules(shrules.DEFAULT_RULES, mesh):
        params, opt_state, psh, ssh = init_pod_state(cfg, optimizer, mesh,
                                                     n_pods, seed)
        jit_local = jax.jit(local, in_shardings=(psh, ssh, None),
                            out_shardings=(psh, ssh, None),
                            donate_argnums=(0, 1))
        jit_fused = jax.jit(fused, in_shardings=(psh, ssh, None),
                            out_shardings=(psh, ssh, None),
                            donate_argnums=(0, 1))

        streams = [TokenStream(cfg.vocab_size, seq_len, batch_per_node,
                               node_index=i, num_nodes=n_pods, seed=seed)
                   for i in range(n_pods)]

        # bytes one pod ships per gossip round per link: the mixed payload
        # is the per-pod parameter pytree (mix_target="params"), so the
        # pod-stacked leaves divide by n_pods
        param_bytes = sum(leaf.size * leaf.dtype.itemsize
                          for leaf in jax.tree_util.tree_leaves(params))
        param_bytes_per_pod = param_bytes / max(n_pods, 1)

        if dryrun:
            nexts = [next(s) for s in streams]
            batch = {"tokens": jnp.stack([b["tokens"] for b in nexts]),
                     "labels": jnp.stack([b["labels"] for b in nexts])}
            extras = {"dryrun": True, "n_pods": n_pods, "k": k,
                      "param_bytes": param_bytes_per_pod}
            for name, fn in (("local", jit_local), ("fused", jit_fused)):
                t0 = time.time()
                fn.lower(params, opt_state, batch).compile()
                dt = time.time() - t0
                extras[f"{name}_compile_s"] = round(dt, 2)
                if tracer is not None:
                    tracer.add_host_span(f"compile:{name}",
                                         tracer.now() - dt, dt,
                                         track="launch")
            for s in streams:
                s.close()
            return TrainReport(steps=0, losses=[], comm_rounds=0,
                               sim_time_units=0.0, extras=extras)

        mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        start_step = 0
        resumed = None
        if mgr is not None:
            got = mgr.restore_latest((params, opt_state))
            if got is not None:
                start_step, (params, opt_state), _ = got
                resumed = start_step

        losses = []
        comm_rounds = 0
        sim_time = 0.0
        step_walls: list[float] = []
        step_comm: list[bool] = []
        for t in range(start_step + 1, steps + 1):
            nexts = [next(s) for s in streams]  # disjoint per-pod shards
            batch = {"tokens": jnp.stack([b["tokens"] for b in nexts]),
                     "labels": jnp.stack([b["labels"] for b in nexts])}
            comm = schedule.is_comm_step(t)
            step_fn = jit_fused if comm else jit_local
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            sim_time += 1.0 / n_pods + (k * r_estimate if comm else 0.0)
            comm_rounds += int(comm)
            loss = float(jnp.mean(metrics["loss"]))  # blocks on the step
            wall = time.perf_counter() - t0
            step_walls.append(wall)
            step_comm.append(comm)
            if tracer is not None:
                tracer.add_host_span("fused_step" if comm else "local_step",
                                     tracer.now() - wall, wall,
                                     track="launch", t=t)
            losses.append(loss)
            if log_every and t % log_every == 0:
                print(f"[train] step {t} loss {loss:.4f} "
                      f"comm_rounds {comm_rounds} sim_time {sim_time:.2f}",
                      flush=True)
            if mgr is not None and t % ckpt_every == 0:
                mgr.save(t, (params, opt_state), extra={"step": t})
        if mgr is not None:
            mgr.wait()
        for s in streams:
            s.close()
        return TrainReport(steps=steps, losses=losses,
                           comm_rounds=comm_rounds,
                           sim_time_units=sim_time, resumed_from=resumed,
                           extras={"param_bytes": param_bytes_per_pod,
                                   "step_walls": step_walls,
                                   "step_comm": step_comm})
