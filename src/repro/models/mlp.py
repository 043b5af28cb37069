"""Feed-forward blocks: dense (SwiGLU / squared-ReLU / GELU) and
Mixture-of-Experts with shared experts + top-k token-choice routing.

The MoE has one router and two dispatches (`moe_apply`). The held-expert
layer sorts the assignments that land on the experts this config holds
and runs them as grouped matmuls, dropping nothing. The capacity layer
uses the sort-based fixed-capacity scheme (no (tokens x experts x
capacity) one-hot): flatten token assignments, sort by expert id, compute
each token's slot inside its expert segment, and gather into an
(experts, capacity, d) buffer (one overflow row absorbs drops). Its experts
are sharded over the `model` mesh axis (EP); tokens are model-replicated
after the attention all-reduce, so dispatch/combine stay device-local and
the only MoE collective is the usual TP reduction of the output.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, p, pz, rms_norm
from repro.runtime.sharding import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> PyTree:
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    prm = {
        "norm": pz((D,), ("embed",), jnp.float32),
        "w_up": p(ks[0], (D, F), ("embed", "mlp"), cfg.dtype),
        "w_down": p(ks[1], (F, D), ("mlp", "embed"), cfg.dtype),
    }
    if cfg.mlp_act == "swiglu":
        prm["w_gate"] = p(ks[2], (D, F), ("embed", "mlp"), cfg.dtype)
    return prm


def _ffn(prm, h, cfg: ModelConfig):
    # Default: sequence-parallel MLP -- tokens stay sharded over
    # ('data','model'), every device runs the FULL d_ff for its token shard.
    # Identical FLOPs to Megatron TP-MLP with ZERO model-axis activation
    # collectives, but each device gathers the full (D,F) weights per layer.
    # For very wide FFNs (qwen110b d_ff=49152) the weight gathers dominate,
    # so cfg.mlp_tp selects the classic Megatron split: d_ff sharded over
    # 'model', residual gathered/reduced. (EXPERIMENTS.md section Perf.)
    tok_axes = (("batch", "seq", "embed_act") if cfg.mlp_tp
                else ("batch", "seq_sp", "embed_act"))
    act_axes = (("batch", "seq", "mlp") if cfg.mlp_tp
                else ("batch", "seq_sp", None))
    h = constrain(h, tok_axes)
    up = jnp.einsum("bsd,df->bsf", h, prm["w_up"])
    if cfg.mlp_act == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", h, prm["w_gate"])
        act = jax.nn.silu(gate) * up
    elif cfg.mlp_act == "squared_relu":
        r = jnp.maximum(up, 0.0)
        act = r * r
    else:
        act = jax.nn.gelu(up)
    act = constrain(act, act_axes)
    return jnp.einsum("bsf,fd->bsd", act, prm["w_down"])


def mlp_apply(prm, x, cfg: ModelConfig, d_ff: int | None = None) -> jax.Array:
    h = rms_norm(x, prm["norm"])
    out = _ffn(prm, h, cfg)
    return constrain(out, ("batch", "seq_sp", "embed_act"))


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig) -> PyTree:
    """The held experts (`cfg.moe_experts` of them) and a router over all
    `cfg.router_width` experts. Each expert's weights are scaled by their
    own fan-in (D, then F), not by the leading expert dimension."""
    ks = jax.random.split(key, 6)
    D, E = cfg.d_model, cfg.moe_experts
    F = cfg.moe_d_ff or cfg.d_ff
    prm = {
        "norm": pz((D,), ("embed",), jnp.float32),
        "router": p(ks[0], (D, cfg.router_width), ("embed", "experts"),
                    jnp.float32),
        "w_up": p(ks[1], (E, D, F), ("experts", "embed", "expert_mlp"),
                  cfg.dtype, scale=D ** -0.5),
        "w_gate": p(ks[2], (E, D, F), ("experts", "embed", "expert_mlp"),
                    cfg.dtype, scale=D ** -0.5),
        "w_down": p(ks[3], (E, F, D), ("experts", "expert_mlp", "embed"),
                    cfg.dtype, scale=F ** -0.5),
    }
    if cfg.moe_shared > 0:
        prm["shared"] = mlp_init(ks[4], cfg,
                                 d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.moe_shared)
        del prm["shared"]["norm"]  # shares the block norm
    return prm


def route(h: jax.Array, router: jax.Array, cfg: ModelConfig):
    """Token-choice routing over all `router_width` experts. h: (N, D).

    Returns (scores (N, E_all) softmax in fp32, gates (N, K), ids (N, K)):
    greedy top-k of the scores, renormalised to sum 1 when
    `cfg.moe_norm_topk`, else scaled by `cfg.moe_routed_scale`
    (DeepSeek-V2's `norm_topk_prob` / `routed_scaling_factor`). The router
    product runs at full fp32 precision, as the published gate does."""
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(scores, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        gates = gates * cfg.moe_routed_scale
    return scores, gates, ids


def seq_balance_loss(scores: jax.Array, ids: jax.Array, batch: int,
                     cfg: ModelConfig) -> jax.Array:
    """DeepSeek-V2's sequence-wise expert-balance loss over all experts:
    per sequence f_i = E / (K S) * #{t: i in topK(t)} and P_i = mean_t
    s_{i,t}; alpha * sum_i f_i P_i, averaged over the sequences."""
    E, K = cfg.router_width, cfg.moe_top_k
    scores = scores.reshape(batch, -1, E)
    S = scores.shape[1]
    picked = jax.nn.one_hot(ids.reshape(batch, S * K), E,
                            dtype=jnp.float32).sum(axis=1)   # (B, E)
    f = picked * (E / (K * S))
    P = scores.mean(axis=1)
    return cfg.moe_seq_aux * jnp.mean(jnp.sum(f * P, axis=-1))


def _held_experts(h, gates, ids, w_up, w_gate, w_down, cfg: ModelConfig):
    """The held experts' part of the routed output, dropping nothing.

    h: (N, D); gates, ids: (N, K) over all experts. The assignments that
    land on a held expert (ids in [offset, offset + E)) are sorted by
    expert, gathered, and run through each expert's SwiGLU as grouped
    matmuls (`lax.ragged_dot`, which computes only the rows of its
    groups); the others sort last and contribute zero. Returns the (N, D)
    fp32 output and the assignments per held expert (E,). The buffer
    holds all N*K assignments, so none is dropped.

    The rows past the groups are masked to zero at every grouped matmul's
    input and output: the TPU's grouped matmul leaves them unwritten, in
    its results and in the gradients of its operands, and unmasked they
    would reach the tokens' gradients through the gather's transpose."""
    N, D = h.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    local = ids - cfg.moe_expert_offset
    held = (local >= 0) & (local < E)
    key = jnp.where(held, local, E).reshape(N * K)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(E, dtype=key.dtype),
                          axis=0, dtype=jnp.int32)
    rows = (jnp.arange(N * K) < jnp.sum(group_sizes))[:, None]

    def grouped(lhs, w):
        return jnp.where(rows, jax.lax.ragged_dot(lhs, w, group_sizes), 0)

    x = jnp.where(rows, jnp.take(h, order // K, axis=0), 0)  # (N*K, D)
    up = grouped(x, w_up)
    gate = grouped(x, w_gate)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(h.dtype)
    y = grouped(act, w_down)                                 # sorted order
    # back to assignment order, one of the k choices at a time (no
    # (N, K, D) tensor): a gather by the inverse permutation; rows past
    # the groups are not the held experts' and are masked, not weighted
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * K, dtype=order.dtype)).reshape(N, K)
    out = jnp.zeros((N, D), jnp.float32)
    for k in range(K):
        picked = jnp.take(y, inverse[:, k], axis=0).astype(jnp.float32)
        out = out + jnp.where(held[:, k:k + 1], picked * gates[:, k:k + 1],
                              0.0)
    return out, group_sizes


def _dispatch_indices(expert_ids: jax.Array, num_experts: int, capacity: int):
    """Sort-based slotting. expert_ids: (A,) flat assignments.

    Returns flat destination index in [0, E*C] for each assignment, where
    E*C is the overflow slot (dropped tokens).
    """
    A = expert_ids.shape[0]
    sort_idx = jnp.argsort(expert_ids)                  # stable
    sorted_ids = expert_ids[sort_idx]
    seg_starts = jnp.searchsorted(sorted_ids, jnp.arange(num_experts))
    pos_in_expert = jnp.arange(A) - seg_starts[sorted_ids]
    dest_sorted = jnp.where(pos_in_expert < capacity,
                            sorted_ids * capacity + pos_in_expert,
                            num_experts * capacity)
    dest = jnp.zeros((A,), dest_sorted.dtype).at[sort_idx].set(dest_sorted)
    return dest


def _moe_grouped(tokens, gates, ids, w_up, w_gate, w_down, cfg: ModelConfig,
                 capacity: int):
    """Run experts for G dispatch groups at a fixed capacity. tokens:
    (G, Nl, D), G sharded over 'data', experts over 'model'; gates, ids:
    (G, Nl, K). Returns the output and the assignments dropped.

    Dispatch is GATHER-based: a cheap per-group 1-D index scatter builds the
    inverse map slot -> source token, then the (G, E, C, D) expert inputs are
    a batched gather (scattering (Nl*K, D) token payloads lowers
    catastrophically in SPMD -- it materialized a u32[(EC+1), D] index
    tensor; gathers do not). All large intermediates carry explicit sharding
    constraints: (G -> data, E -> model)."""
    G, Nl, D = tokens.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    C = capacity

    dest = jax.vmap(
        lambda i: _dispatch_indices(i, E, C))(ids.reshape(G, Nl * K))
    dest = constrain(dest, ("batch", None))                  # (G, Nl*K) int
    # inverse map per group: which assignment fills expert slot s
    slot_src = jnp.full((G, E * C + 1), Nl * K, jnp.int32)
    slot_src = jax.vmap(lambda s, d: s.at[d].set(
        jnp.arange(Nl * K, dtype=jnp.int32)))(slot_src, dest)
    slot_src = slot_src[:, :E * C]                           # (G, E*C)
    slot_valid = slot_src < Nl * K
    token_src = jnp.where(slot_valid, slot_src // K, 0)
    expert_in = jnp.take_along_axis(
        tokens, token_src[..., None], axis=1)                # (G, E*C, D)
    expert_in = jnp.where(slot_valid[..., None], expert_in, 0)
    expert_in = expert_in.reshape(G, E, C, D)
    expert_in = constrain(expert_in, ("batch", "experts", None, "embed_act"))

    up = jnp.einsum("gecd,edf->gecf", expert_in, w_up)
    gate = jnp.einsum("gecd,edf->gecf", expert_in, w_gate)
    act = jax.nn.silu(gate) * up
    act = constrain(act, ("batch", "experts", None, "expert_mlp"))
    expert_out = jnp.einsum("gecf,efd->gecd", act, w_down)
    expert_out = constrain(expert_out,
                           ("batch", "experts", None, "embed_act"))

    flat_out = jnp.concatenate(
        [expert_out.reshape(G, E * C, D),
         jnp.zeros((G, 1, D), expert_out.dtype)], axis=1)
    out = jnp.zeros((G, Nl, D), jnp.float32)
    for k in range(K):  # accumulate per assignment; no (G,Nl,K,D) tensor
        picked = jnp.take_along_axis(
            flat_out, dest.reshape(G, Nl, K)[:, :, k][..., None], axis=1)
        out = out + picked.astype(jnp.float32) * gates[:, :, k:k + 1]
    out = constrain(out, ("batch", None, "embed_act"))
    return out.astype(tokens.dtype), jnp.sum(dest == E * C, dtype=jnp.int32)


def moe_apply(prm, x, cfg: ModelConfig, groups: int = 1):
    """Token-choice top-k MoE with optional shared experts. x: (B,S,D).

    Returns (output, stats): stats holds `aux`, the sequence-wise balance
    loss (0 unless `cfg.moe_seq_aux`), `expert_tokens`, the assignments
    routed to each held expert, and `dropped`, the assignments over an
    expert's capacity (0 on the held-expert layer, which drops none).

    Two dispatches behind one router (`route`):
      * `moe_capacity_factor` 0: the held-expert layer (`_held_experts`):
        this config holds experts [offset, offset + moe_experts) of
        `router_width`, routes every token over all of them and computes
        its own experts' part, dropping nothing. On one chip that is
        expert parallelism without its exchange.
      * `moe_capacity_factor` > 0: every expert held, sharded over the
        mesh's 'model' axis by GSPMD, each with a fixed capacity per
        dispatch group, C = ceil(top_k * tokens_per_group * cf / E);
        overflow drops. `groups` partitions the tokens (the launcher sets
        the data-axis size) so dispatch and combine stay device-local.
    """
    B, S, D = x.shape
    E = cfg.moe_experts
    N = B * S
    with jax.named_scope("lm.moe.route"):
        h = rms_norm(x, prm["norm"])
        scores, gates, ids = route(h.reshape(N, D), prm["router"], cfg)
        aux = (seq_balance_loss(scores, ids, B, cfg) if cfg.moe_seq_aux
               else jnp.zeros((), jnp.float32))
    with jax.named_scope("lm.moe.experts"):
        if cfg.moe_capacity_factor > 0:
            if cfg.router_width != E:
                raise ValueError("a capacity-dispatched MoE holds every "
                                 "expert; set moe_capacity_factor 0 for a "
                                 "share of them")
            G = groups if N % groups == 0 else 1
            Nl = N // G
            C = max(1, int(-(-cfg.moe_top_k * Nl * cfg.moe_capacity_factor
                             // E)))
            tokens = constrain(h.reshape(G, Nl, D),
                               ("batch", None, "embed_act"))
            combined, dropped = _moe_grouped(
                tokens, gates.reshape(G, Nl, -1), ids.reshape(G, Nl, -1),
                prm["w_up"], prm["w_gate"], prm["w_down"], cfg, C)
            counts = jnp.zeros((E,), jnp.int32).at[ids.reshape(-1)].add(1)
        else:
            combined, counts = _held_experts(
                h.reshape(N, D), gates, ids, prm["w_up"], prm["w_gate"],
                prm["w_down"], cfg)
            dropped = jnp.zeros((), jnp.int32)
    out = combined.reshape(B, S, D).astype(x.dtype)
    if "shared" in prm:
        with jax.named_scope("lm.moe.shared"):
            out = out + _ffn(prm["shared"], h, cfg)
    stats = {"aux": aux, "expert_tokens": counts, "dropped": dropped}
    return constrain(out, ("batch", "seq", "embed_act")), stats
