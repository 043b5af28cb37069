"""Step factories: train_step / serve_step, plus the consensus (multi-pod)
wrappers that realize the paper's algorithm at pod scale.

Consensus mode runs one step per pod on its own parameter replica: where a
pod is one device, as a `jax.shard_map` manual over the `pod` mesh axis, so
the replica's program has no batch dimension over replicas (a grouped
matmul takes none); where a pod spans devices, as a `vmap` over the pod
dimension in which each pod runs a standard GSPMD-auto (data=FSDP,
model=TP) step. The paper's mixing z <- Pz (or parameter gossip) is a
collective over the 'pod' axis. Cheap iterations compile WITHOUT any
cross-pod collective; expensive iterations carry exactly the graph's
ppermutes/all-reduce -- the launcher alternates per the schedule, so the
communication pattern is explicit in each compiled program (never hidden in
traced control flow).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.consensus import tree_mix_collective
from repro.core.graphs import CommGraph
from repro.models import transformer
from repro.models.common import ModelConfig
from repro.optim import Optimizer, OptState

PyTree = Any


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    moe_groups: int = 1, microbatches: int = 1):
    """Pure synchronous step: (params, opt_state, batch) ->
    (params, opt_state, metrics). Gradients are averaged over the full batch
    (GSPMD reduces over the data axis automatically).

    `microbatches` > 1 runs gradient accumulation: the batch is split along
    its leading dim and a scan accumulates fp32 grads, dividing the
    activation working set by the microbatch count (the production lever
    that fits large-model training in HBM; optimizer state and params are
    untouched).

    The metrics hold the loss (cross-entropy plus any MoE balance loss),
    the gradient norm, and the MoE layers' counters: `expert_tokens`
    (moe layers, held experts), the assignments routed to each held
    expert, and `dropped`, those over an expert's capacity (0 on the
    held-expert layer, which drops none)."""

    def grad_fn(params, batch):
        (loss, stats), grads = jax.value_and_grad(
            transformer.loss_and_stats, has_aux=True)(
            params, batch, cfg, moe_groups)
        return loss, grads, stats

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads, stats = grad_fn(params, batch)
        else:
            def resh(a):
                return a.reshape((microbatches, a.shape[0] // microbatches)
                                 + a.shape[1:])
            mb = jax.tree.map(resh, batch)
            zero = jax.tree.map(
                lambda p_: jnp.zeros(p_.shape, jnp.float32), params)

            def acc_fn(carry, mbatch):
                loss_acc, g_acc = carry
                loss, g, stats = grad_fn(params, mbatch)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (loss_acc + loss, g_acc), stats

            (loss, grads), stats = jax.lax.scan(
                acc_fn, (jnp.zeros((), jnp.float32), zero), mb)
            stats = jax.tree.map(lambda a: jnp.sum(a, axis=0), stats)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        with jax.named_scope("lm.optimizer"):
            new_params, new_state = optimizer.update(grads, opt_state,
                                                     params)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        return new_params, new_state, {
            "loss": loss, "grad_norm": gnorm,
            "expert_tokens": stats["expert_tokens"],
            "dropped": stats["dropped"]}

    return train_step


def make_prefill_step(cfg: ModelConfig, moe_groups: int = 1):
    """Forward-only (inference prefill): returns last-position logits."""

    def prefill_step(params, batch):
        logits = transformer.forward(params, batch["tokens"], cfg,
                                     enc=batch.get("enc"),
                                     moe_groups=moe_groups)
        return logits[:, -1, :]

    return prefill_step


def make_serve_step(cfg: ModelConfig, moe_groups: int = 1):
    """One-token decode: (params, cache, tokens, pos) -> (logits, cache)."""

    def serve_step(params, cache, tokens, pos):
        return transformer.decode_step(params, cache, tokens, pos, cfg,
                                       moe_groups=moe_groups)

    return serve_step


# ---------------------------------------------------------------------------
# Consensus (multi-pod) wrappers -- the paper's technique as a feature
# ---------------------------------------------------------------------------


def per_replica(fn, mesh):
    """`fn` of one replica's (unstacked) arguments, applied to pod-stacked
    ones: a shard_map manual over 'pod' (the other mesh axes stay GSPMD-
    auto), so each device group runs its own replica's program with no
    replica dimension in it."""
    def body(*args):
        out = fn(*jax.tree.map(lambda x: x[0], args))
        return jax.tree.map(lambda x: x[None], out)

    def apply(*args):
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P("pod"),) * len(args),
                             out_specs=P("pod"), axis_names={"pod"},
                             check_vma=False)(*args)
    return apply


def make_consensus_steps(cfg: ModelConfig, optimizer: Optimizer,
                         graph: CommGraph, mesh,
                         moe_groups: int = 1,
                         mix_target: str = "params",
                         microbatches: int = 1):
    """Returns (local_step, mix_step, fused_step) for consensus training.

    ALL state (params, every optimizer leaf including the step counter)
    carries a leading pod-replica dim of size graph.n = number of pods,
    sharded P('pod', ...). `mix_target` selects WHAT the consensus averages:
      "params" -- gossip parameter averaging (consensus-SGD; section VI mode)
      "z"      -- faithful DDA: mix the dual (accumulated-gradient) state
                  held by the dual_averaging optimizer.

    local_step: one optimizer step per pod on its own data shard; NO
      cross-pod communication (the paper's cheap iteration, cost 1/n).
      Realized as `per_replica(inner)` (a shard_map manual over 'pod')
      where a pod is one device, else as jax.vmap(inner,
      spmd_axis_name='pod'): the vmap batching rule prepends 'pod' to
      every internal sharding constraint, so each pod runs FSDP+TP over
      (data, model) on its own replica.
    mix_step: consensus mixing only (the communication half of an expensive
      iteration, cost kr) -- a pod-manual shard_map whose body is the
      graph's ppermutes/all-reduce + weighted accumulation, nothing else.
    fused_step: local + mix in one program (expensive iteration, 1/n + kr);
      mixing is expressed as the doubly-stochastic P einsum over the pod
      dim, which GSPMD partitions into cross-pod collectives; the mix is
      the named scope `lm.gossip`.
    """
    inner = make_train_step(cfg, optimizer, moe_groups,
                            microbatches=microbatches)
    pod_devices = [n for n, size in zip(mesh.axis_names, mesh.devices.shape)
                   if n != "pod" and size > 1]
    # one device a replica: the replica's own program, with no replica
    # dimension (the held experts' grouped matmul takes none on a TPU);
    # a replica over several devices: vmap with 'pod' prepended to every
    # sharding, as XLA's SPMD partitioner fails a partial-manual
    # shard_map whose auto axes are wider than 1
    replica_step = (jax.vmap(inner, spmd_axis_name="pod") if pod_devices
                    else per_replica(inner, mesh))

    def local_step(params, opt_state, batch):
        return replica_step(params, opt_state, batch)
    Pmat = jnp.asarray(graph.mixing_matrix(), jnp.float32)

    def _dense_mix(tree):
        # full float32 products: at the TPU's default precision the weights
        # and operands would be rounded to bfloat16 at every gossip round
        return jax.tree.map(
            lambda a: jnp.einsum("pq,q...->p...", Pmat,
                                 a.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST
                                 ).astype(a.dtype),
            tree)

    def mix_body(params, opt_state):
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        unsq = lambda t: jax.tree.map(lambda x: x[None], t)
        if mix_target == "params":
            mixed = tree_mix_collective(sq(params), graph, "pod")
            return unsq(mixed), opt_state
        mixed_z = tree_mix_collective(sq(opt_state.inner["z"]), graph, "pod")
        return params, OptState(opt_state.step, {"z": unsq(mixed_z)})

    mix = jax.shard_map(mix_body, mesh=mesh,
                        in_specs=(P("pod"), P("pod")),
                        out_specs=(P("pod"), P("pod")),
                        axis_names={"pod"}, check_vma=False)

    def fused_step(params, opt_state, batch):
        params, opt_state, metrics = local_step(params, opt_state, batch)
        with jax.named_scope("lm.gossip"):
            if mix_target == "params":
                params = _dense_mix(params)
            else:
                opt_state = OptState(opt_state.step,
                                     {"z": _dense_mix(opt_state.inner["z"])})
        return params, opt_state, metrics

    return local_step, mix, fused_step
