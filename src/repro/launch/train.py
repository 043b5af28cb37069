"""Training driver: consensus data-parallel LM training with the paper's
communication schedules, checkpoint/restart, and optional straggler
simulation. This is the host loop the examples use; on a real cluster each
pod's process group runs exactly this with the mesh spanning its slice.

The schedule decides per iteration whether to run the cheap `local_step`
(no cross-pod collective) or the `fused_step` (local + consensus mixing) --
the paper's 1/n vs 1/n + kr cost split is directly visible as two compiled
programs. `ConsensusProgram` holds those programs and the weights' init,
compiled once, so that a server runs one training request after another
through them, each with weights and token streams drawn from its own seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core.graphs import CommGraph, build_graph
from repro.core.schedules import CommSchedule, EveryIteration
from repro.data.pipeline import TokenStream
from repro.launch import specs as sp
from repro.launch.mesh import num_pods
from repro.launch.steps import make_consensus_steps
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
    init_all, psh, ssh = _pod_init(cfg, optimizer, mesh, n_pods)
    params, opt_state = jax.jit(init_all, out_shardings=(psh, ssh))(
        jax.random.PRNGKey(seed))
    return params, opt_state, psh, ssh


def _pod_init(cfg: ModelConfig, optimizer: Optimizer, mesh, n_pods: int):
    """(init of every replica from one key, params' and state's shardings)."""
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

    return init_all, psh, ssh


class ConsensusProgram:
    """The compiled programs of one consensus training configuration:
    the replicas' init, the cheap `local` step and the `fused` step (local
    + gossip), jitted once for `cfg`, `optimizer`, `graph` on `mesh` and
    one per-replica batch shape, and compiled ahead of time at their first
    call. Holding the object holds the executables: `run` draws weights
    and token streams from its seed through them, so that a second run
    compiles nothing (`compiles` counts the compiles, each the `compile`
    span of the tracer in use)."""

    def __init__(self, cfg: ModelConfig, optimizer: Optimizer, mesh,
                 graph: CommGraph, *, batch_per_node: int, seq_len: int,
                 mix_target: str = "params"):
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.n_pods = axis_sizes.get("pod", 1)
        if graph.n != self.n_pods:
            raise ValueError(f"graph has n={graph.n} but the mesh has "
                             f"{self.n_pods} pods")
        self.cfg, self.mesh, self.graph = cfg, mesh, graph
        self.batch_per_node, self.seq_len = batch_per_node, seq_len
        self.compiles = 0
        #: each program's compile wall, seconds
        self.compile_s: dict[str, float] = {}
        self._executables: dict[str, Any] = {}
        local, _, fused = make_consensus_steps(
            cfg, optimizer, graph, mesh,
            moe_groups=max(axis_sizes.get("data", 1), 1)
            if cfg.moe_experts else 1,
            mix_target=mix_target)
        with self._rules():
            init_all, psh, ssh = _pod_init(cfg, optimizer, mesh, self.n_pods)
        #: the shardings of the replicas' (params, opt_state)
        self.state_shardings = (psh, ssh)
        self.batch_sharding = NamedSharding(mesh, P("pod"))
        self._jits = {
            "init": jax.jit(init_all, out_shardings=(psh, ssh)),
            "local": jax.jit(local, in_shardings=(psh, ssh,
                                                  self.batch_sharding),
                             out_shardings=(psh, ssh, None),
                             donate_argnums=(0, 1)),
            "fused": jax.jit(fused, in_shardings=(psh, ssh,
                                                  self.batch_sharding),
                             out_shardings=(psh, ssh, None),
                             donate_argnums=(0, 1)),
        }

    def _rules(self):
        return shrules.use_rules(shrules.DEFAULT_RULES, self.mesh)

    def executable(self, name: str, args: tuple, tracer=None):
        """The compiled program `name` ("init", "local", "fused") for
        `args`, compiling it at its first call."""
        exe = self._executables.get(name)
        if exe is None:
            span = (tracer.span("compile") if tracer is not None
                    else contextlib.nullcontext())
            t0 = time.perf_counter()
            with span, self._rules():
                exe = self._jits[name].lower(*args).compile()
            self.compile_s[name] = time.perf_counter() - t0
            self._executables[name] = exe
            self.compiles += 1
        return exe

    def init(self, seed: int, tracer=None):
        """Every replica's (params, opt_state), drawn from `seed`."""
        key = jax.random.PRNGKey(seed)
        return self.executable("init", (key,), tracer)(key)

    def streams(self, seed: int) -> list[TokenStream]:
        """The replicas' disjoint token streams for `seed`."""
        return [TokenStream(self.cfg.vocab_size, self.seq_len,
                            self.batch_per_node, node_index=i,
                            num_nodes=self.n_pods, seed=seed)
                for i in range(self.n_pods)]

    def next_batch(self, streams: list[TokenStream]) -> dict:
        """The replicas' next batch, replica i's rows put on its devices."""
        toks = np.stack([s.next_host() for s in streams])
        return jax.device_put({"tokens": toks[:, :, :-1],
                               "labels": toks[:, :, 1:]},
                              self.batch_sharding)

    def dryrun(self, tracer=None) -> TrainReport:
        """Compile both step programs and run no step: their compile
        walls and the bytes one replica's parameters take, in `extras`."""
        params, opt_state = self.init(0, tracer)
        streams = self.streams(0)
        batch = self.next_batch(streams)
        for s in streams:
            s.close()
        extras = {"dryrun": True, "n_pods": self.n_pods,
                  "k": self.graph.degree,
                  "param_bytes": _param_bytes(params) / self.n_pods}
        for name in ("local", "fused"):
            self.executable(name, (params, opt_state, batch), tracer)
            dt = self.compile_s[name]
            extras[f"{name}_compile_s"] = round(dt, 2)
            if tracer is not None:
                tracer.add_host_span(f"compile:{name}", tracer.now() - dt,
                                     dt, track="launch")
        return TrainReport(steps=0, losses=[], comm_rounds=0,
                           sim_time_units=0.0, extras=extras)

    def run(self, *, steps: int, schedule: CommSchedule, seed: int,
            r_estimate: float = 0.05, log_every: int = 0,
            ckpt_dir: str | None = None, ckpt_every: int = 50,
            tracer=None) -> TrainReport:
        """One training run of `steps` steps from the weights and token
        streams `seed` draws. The tracer's spans name the host phases of
        each step: `batch` (the replicas' next tokens, put on their
        devices), `dispatch` (queueing the step program) and `wait`
        (blocking on its loss and counters)."""
        def span(name):
            return (tracer.span(name) if tracer is not None
                    else contextlib.nullcontext())

        compiles0 = self.compiles
        n_pods, k = self.n_pods, self.graph.degree
        params, opt_state = self.init(seed, tracer)
        param_bytes = _param_bytes(params) / max(n_pods, 1)
        streams = self.streams(seed)

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
        expert_tokens = None
        dropped = 0
        try:
            for t in range(start_step + 1, steps + 1):
                with span("batch"):
                    batch = self.next_batch(streams)
                comm = schedule.is_comm_step(t)
                name = "fused" if comm else "local"
                step_fn = self.executable(name, (params, opt_state, batch),
                                          tracer)
                t0 = time.perf_counter()
                with span("dispatch"):
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
                sim_time += 1.0 / n_pods + (k * r_estimate if comm else 0.0)
                comm_rounds += int(comm)
                with span("wait"):
                    loss = float(jnp.mean(metrics["loss"]))  # blocks
                    routed = np.asarray(metrics["expert_tokens"], np.int64)
                    dropped += int(np.sum(np.asarray(metrics["dropped"])))
                wall = time.perf_counter() - t0
                routed = routed.sum(axis=0)  # over replicas
                expert_tokens = (routed if expert_tokens is None
                                 else expert_tokens + routed)
                step_walls.append(wall)
                step_comm.append(comm)
                if tracer is not None:
                    tracer.add_host_span(f"{name}_step",
                                         tracer.now() - wall, wall,
                                         track="launch", t=t)
                losses.append(loss)
                if log_every and t % log_every == 0:
                    print(f"[train] step {t} loss {loss:.4f} "
                          f"comm_rounds {comm_rounds} "
                          f"sim_time {sim_time:.2f}", flush=True)
                if mgr is not None and t % ckpt_every == 0:
                    mgr.save(t, (params, opt_state), extra={"step": t})
            if mgr is not None:
                mgr.wait()
        finally:
            for s in streams:
                s.close()
        extras = {"param_bytes": param_bytes, "step_walls": step_walls,
                  "step_comm": step_comm,
                  "programs_compiled": self.compiles - compiles0,
                  "dropped_tokens": dropped}
        if expert_tokens is not None and expert_tokens.size:
            extras["expert_tokens"] = expert_tokens.tolist()
        return TrainReport(steps=steps, losses=losses,
                           comm_rounds=comm_rounds,
                           sim_time_units=sim_time, resumed_from=resumed,
                           extras=extras)


def _param_bytes(tree) -> float:
    return float(sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree_util.tree_leaves(tree)))


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

    `graph` overrides the `topology` name with a prebuilt CommGraph (n
    must equal the mesh's pod-axis size). `dryrun` lowers + compiles both
    step programs (cheap local, fused local+mix) and returns after ZERO
    training steps with the compile timings in `extras` -- the CI smoke
    mode. A server that runs many requests holds a `ConsensusProgram`
    instead (the experiments runner leases one from its compile cache).

    `tracer` (optional `repro.obs.Tracer`) receives host-clock spans per
    training step / compile; the per-step walls and comm flags are also
    returned in `extras["step_walls"]` / `extras["step_comm"]` so the
    experiments runner can quote step-time quantiles without a tracer.
    """
    if graph is None:
        graph = build_graph(topology, num_pods(mesh))
    program = ConsensusProgram(cfg, optimizer, mesh, graph,
                               batch_per_node=batch_per_node,
                               seq_len=seq_len, mix_target=mix_target)
    if dryrun:
        return program.dryrun(tracer)
    return program.run(steps=steps, schedule=schedule or EveryIteration(),
                       seed=seed, r_estimate=r_estimate,
                       log_every=log_every, ckpt_dir=ckpt_dir,
                       ckpt_every=ckpt_every, tracer=tracer)
