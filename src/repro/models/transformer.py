"""Decoder assembly: embeddings + (prologue blocks + scanned superblocks) +
final norm + LM head, with forward (train/prefill) and one-token decode.

The layer stack is `cfg.prologue` followed by `cfg.n_super` repetitions of
`cfg.superblock`. Per-slot parameters are STACKED over the superblock
repetitions and the stack runs under `jax.lax.scan` (keeps HLO size O(1) in
depth -- essential for 80-100 layer dry-runs) with per-superblock remat.

Supported block kinds (see ModelConfig): attn, attn_moe, mla, mla_moe,
cross_attn, mamba1, mamba2, shared_attn. "shared_attn" uses ONE weight copy
(zamba2-style) plus per-repetition LoRA deltas that ARE stacked.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ModelConfig, barrier, cross_entropy_loss, p,
                                 pz, rms_norm, split_axes)
from repro.runtime.sharding import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# Per-block init/apply/decode dispatch
# ---------------------------------------------------------------------------


def _block_init(kind: str, key, cfg: ModelConfig) -> PyTree:
    if kind == "attn":
        k1, k2 = jax.random.split(key)
        return {"attn": attn.gqa_init(k1, cfg), "mlp": mlp_mod.mlp_init(k2, cfg)}
    if kind == "attn_moe":
        k1, k2 = jax.random.split(key)
        return {"attn": attn.gqa_init(k1, cfg), "moe": mlp_mod.moe_init(k2, cfg)}
    if kind == "mla":
        k1, k2 = jax.random.split(key)
        return {"attn": attn.mla_init(k1, cfg), "mlp": mlp_mod.mlp_init(k2, cfg)}
    if kind == "mla_moe":
        k1, k2 = jax.random.split(key)
        return {"attn": attn.mla_init(k1, cfg), "moe": mlp_mod.moe_init(k2, cfg)}
    if kind == "cross_attn":
        k1, k2 = jax.random.split(key)
        return {"attn": attn.cross_attn_init(k1, cfg),
                "mlp": mlp_mod.mlp_init(k2, cfg)}
    if kind == "mamba1":
        return {"mamba": ssm_mod.mamba1_init(key, cfg)}
    if kind == "mamba2":
        return {"mamba": ssm_mod.mamba2_init(key, cfg)}
    if kind == "shared_attn":
        # LoRA deltas only; shared weights live at top level.
        r = cfg.shared_attn_lora
        D, H, hd = cfg.d_model, cfg.num_heads, cfg.hd
        ks = jax.random.split(key, 4)
        return {
            "lora_q_a": p(ks[0], (D, r), ("embed", "lora"), cfg.dtype),
            "lora_q_b": pz((r, H, hd), ("lora", "q_heads", "head"), cfg.dtype),
            "lora_o_a": p(ks[1], (H, hd, r), ("q_heads", "head", "lora"),
                          cfg.dtype),
            "lora_o_b": pz((r, D), ("lora", "embed"), cfg.dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def _mixer_apply(kind: str, prm, x, cfg, positions, shared, enc):
    if kind in ("attn", "attn_moe"):
        return attn.gqa_apply(prm["attn"], x, cfg, positions)
    if kind in ("mla", "mla_moe"):
        return attn.mla_apply(prm["attn"], x, cfg, positions)
    if kind == "cross_attn":
        return attn.cross_attn_apply(prm["attn"], x, enc, cfg)
    if kind == "mamba1":
        return ssm_mod.mamba1_apply(prm["mamba"], x, cfg, positions)
    if kind == "mamba2":
        return ssm_mod.mamba2_apply(prm["mamba"], x, cfg, positions)
    if kind == "shared_attn":
        return _shared_attn_apply(prm, shared["attn"], x, cfg, positions)
    raise ValueError(kind)


#: the named scope of each block kind's mixer in the device trace
_MIXER_SCOPE = {"attn": "lm.attn", "attn_moe": "lm.attn", "mla": "lm.mla",
                "mla_moe": "lm.mla", "cross_attn": "lm.cross_attn",
                "mamba1": "lm.ssm", "mamba2": "lm.ssm",
                "shared_attn": "lm.attn"}


def _block_apply(kind: str, prm, x, cfg: ModelConfig, positions, shared, enc,
                 moe_groups: int):
    """One block; returns (x, the MoE's stats or None)."""
    stats = None
    with jax.named_scope(_MIXER_SCOPE[kind]):
        x = x + _mixer_apply(kind, prm, x, cfg, positions, shared, enc)
    if kind.endswith("_moe"):
        with jax.named_scope("lm.moe"):
            out, stats = mlp_mod.moe_apply(prm["moe"], x, cfg,
                                           groups=moe_groups)
        x = x + out
    elif kind in ("attn", "mla", "cross_attn"):
        with jax.named_scope("lm.mlp"):
            x = x + mlp_mod.mlp_apply(prm["mlp"], x, cfg)
    elif kind == "shared_attn" and shared.get("mlp") is not None:
        with jax.named_scope("lm.mlp"):
            x = x + mlp_mod.mlp_apply(shared["mlp"], x, cfg)
    # mamba1/mamba2 blocks are mixer-only (falcon-mamba has d_ff=0);
    # zamba2's shared block carries the model's single (shared) FFN.
    # The residual stream BETWEEN blocks is sequence-parallel (seq_sp ->
    # model, Megatron SP): it is what the scan checkpoints, so this
    # constraint sets the saved-activation footprint.
    return constrain(x, ("batch", "seq_sp", "embed_act")), stats


def _shared_attn_apply(lora, shared, x, cfg: ModelConfig, positions):
    """zamba2-style weight-shared attention with per-repetition LoRA on the
    q and o projections (simplification of zamba2's shared-block LoRA;
    documented in DESIGN.md)."""
    base = attn.gqa_apply(shared, x, cfg, positions)
    h = rms_norm(x, shared["norm"])
    q_delta = jnp.einsum("bsd,dr->bsr", h, lora["lora_q_a"])
    q_delta = jnp.einsum("bsr,rhk->bshk", q_delta, lora["lora_q_b"])
    o_delta = jnp.einsum("bshk,hkr->bsr", q_delta, lora["lora_o_a"])
    o_delta = jnp.einsum("bsr,rd->bsd", o_delta, lora["lora_o_b"])
    return base + o_delta


# ---------------------------------------------------------------------------
# Cache dispatch
# ---------------------------------------------------------------------------


def _block_init_cache(kind: str, cfg: ModelConfig, batch: int, max_seq: int,
                      dtype) -> PyTree:
    if kind in ("attn", "attn_moe", "shared_attn"):
        return attn.gqa_init_cache(cfg, batch, max_seq, dtype)
    if kind in ("mla", "mla_moe"):
        return attn.mla_init_cache(cfg, batch, max_seq, dtype)
    if kind == "cross_attn":
        K, hd = cfg.num_kv_heads, cfg.hd
        n = cfg.num_encoder_tokens
        return {"ek": jnp.zeros((batch, n, K, hd), dtype),
                "ev": jnp.zeros((batch, n, K, hd), dtype)}
    if kind == "mamba1":
        return ssm_mod.mamba1_init_cache(cfg, batch, dtype)
    if kind == "mamba2":
        return ssm_mod.mamba2_init_cache(cfg, batch, dtype)
    raise ValueError(kind)


def _block_decode(kind: str, prm, x, cache, cfg: ModelConfig, pos, shared,
                  moe_groups: int):
    if kind in ("attn", "attn_moe"):
        out, cache = attn.gqa_decode(prm["attn"], x, cache, cfg, pos)
    elif kind in ("mla", "mla_moe"):
        out, cache = attn.mla_decode(prm["attn"], x, cache, cfg, pos)
    elif kind == "cross_attn":
        out, cache = _cross_decode(prm["attn"], x, cache, cfg)
    elif kind == "mamba1":
        out, cache = ssm_mod.mamba1_decode(prm["mamba"], x, cache, cfg, pos)
    elif kind == "mamba2":
        out, cache = ssm_mod.mamba2_decode(prm["mamba"], x, cache, cfg, pos)
    elif kind == "shared_attn":
        out, cache = _shared_attn_decode(prm, shared["attn"], x, cache, cfg,
                                         pos)
    else:
        raise ValueError(kind)
    x = x + out.astype(x.dtype)  # cache dtype must not promote the carry
    if kind.endswith("_moe"):
        x = x + mlp_mod.moe_apply(prm["moe"], x, cfg, groups=moe_groups)[0]
    elif kind in ("attn", "mla", "cross_attn"):
        x = x + mlp_mod.mlp_apply(prm["mlp"], x, cfg)
    elif kind == "shared_attn" and shared.get("mlp") is not None:
        x = x + mlp_mod.mlp_apply(shared["mlp"], x, cfg)
    return constrain(x, ("batch", "seq", "embed_act")), cache


def _cross_decode(prm, x, cache, cfg: ModelConfig):
    """Decode-time cross attention against PRE-COMPUTED encoder K/V (filled
    at prefill; serve_step receives them as part of the cache)."""
    h = rms_norm(x, prm["norm"])
    q = jnp.einsum("bsd,dhk->bshk", h, prm["wq"])
    B, S, H, hd = q.shape
    K = cache["ek"].shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgh,bnkh->bkgsn", qg, cache["ek"])
    scores = (scores / jnp.sqrt(hd)).astype(jnp.float32)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgsn,bnkh->bskgh", w, cache["ev"]).reshape(B, S, H, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, prm["wo"])
    out = jnp.tanh(prm["gate"].astype(jnp.float32)).astype(x.dtype) * out
    return constrain(out, ("batch", "seq", "embed_act")), cache


def _shared_attn_decode(lora, shared, x, cache, cfg: ModelConfig, pos):
    base, cache = attn.gqa_decode(shared, x, cache, cfg, pos)
    h = rms_norm(x, shared["norm"])
    q_delta = jnp.einsum("bsd,dr->bsr", h, lora["lora_q_a"])
    q_delta = jnp.einsum("bsr,rhk->bshk", q_delta, lora["lora_q_b"])
    o_delta = jnp.einsum("bshk,hkr->bsr", q_delta, lora["lora_o_a"])
    o_delta = jnp.einsum("bsr,rd->bsd", o_delta, lora["lora_o_b"])
    return base + o_delta, cache


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def init(key, cfg: ModelConfig) -> tuple[PyTree, PyTree]:
    """Returns (params, logical_axes) trees."""
    keys = jax.random.split(key, 8)
    pairs: dict[str, Any] = {
        "embed": p(keys[0], (cfg.vocab_size, cfg.d_model),
                   ("vocab", "embed"), cfg.dtype, scale=1.0),
        "final_norm": pz((cfg.d_model,), ("embed",), jnp.float32),
    }
    if not cfg.tie_embeddings:
        pairs["lm_head"] = p(keys[1], (cfg.d_model, cfg.vocab_size),
                             ("embed", "vocab"), cfg.dtype)
    if cfg.prologue:
        pk = jax.random.split(keys[2], len(cfg.prologue))
        pairs["prologue"] = [
            _block_init(kind, pk[i], cfg)
            for i, kind in enumerate(cfg.prologue)]
    if "shared_attn" in cfg.superblock:
        pairs["shared_attn"] = attn.gqa_init(keys[3], cfg)
        if cfg.d_ff > 0:
            pairs["shared_mlp"] = mlp_mod.mlp_init(keys[6], cfg)
    params, axes = split_axes(pairs)

    stack_params: dict[str, Any] = {}
    stack_axes: dict[str, Any] = {}
    for i, kind in enumerate(cfg.superblock):
        _, slot_axes = split_axes(_block_init(kind, keys[5], cfg))

        def one(j, kind=kind, i=i):
            arrays, _ = split_axes(_block_init(
                kind, jax.random.fold_in(keys[4], i * 1000 + j), cfg))
            return arrays

        stack_params[f"slot{i}"] = jax.vmap(one)(jnp.arange(cfg.n_super))
        stack_axes[f"slot{i}"] = jax.tree.map(
            lambda a: ("layers",) + a, slot_axes, is_leaf=_is_axes)
    params["stack"] = stack_params
    axes["stack"] = stack_axes
    return params, axes


def _embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x.astype(cfg.dtype), ("batch", "seq", "embed_act"))


def _unembed(params, x, cfg: ModelConfig):
    x = constrain(x, ("batch", "seq", "embed_act"))  # single seq gather
    x = rms_norm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return constrain(logits, ("batch", "seq", "vocab"))


def _moe_stats(prologue: list, stacked: list, cfg: ModelConfig) -> dict:
    """The MoE layers' stats in layer order: `aux` summed over them,
    `expert_tokens` (moe layers, held experts), `dropped` summed."""
    E = cfg.moe_experts
    per_layer = [s["expert_tokens"][None] for s in prologue]
    if stacked:  # (n_super, E) per MoE slot -> superblock-major layer order
        per_layer.append(jnp.stack([s["expert_tokens"] for s in stacked],
                                   axis=1).reshape(-1, E))
    every = prologue + stacked
    return {
        "aux": sum((jnp.sum(s["aux"]) for s in every),
                   jnp.zeros((), jnp.float32)),
        "expert_tokens": (jnp.concatenate(per_layer) if per_layer
                          else jnp.zeros((0, E), jnp.int32)),
        "dropped": sum((jnp.sum(s["dropped"]) for s in every),
                       jnp.zeros((), jnp.int32)),
    }


def forward(params, tokens, cfg: ModelConfig, enc: jax.Array | None = None,
            moe_groups: int = 1, with_stats: bool = False):
    """Training/prefill forward -> logits (B,S,V). `enc`: (B,N,E) stubbed
    encoder states for VLM cross-attention (precomputed patch embeddings).
    `with_stats` returns (logits, the MoE layers' stats, `_moe_stats`)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = _embed(params, tokens, cfg)

    shared = {"attn": params.get("shared_attn"),
              "mlp": params.get("shared_mlp")}
    prologue_stats = []
    for i, kind in enumerate(cfg.prologue):
        x, st = _block_apply(kind, params["prologue"][i], x, cfg, positions,
                             shared, enc, moe_groups)
        if st is not None:
            prologue_stats.append(st)

    def superblock(x, slot_params):
        # The barrier pins the saved scan carry to bf16: without it XLA
        # hoists the rms_norm upcast through the carry history buffer and
        # stores the full (L, B, S, D) residual stack in f32 (2x memory).
        x = barrier(x)
        stats = []
        for i, kind in enumerate(cfg.superblock):
            x, st = _block_apply(kind, slot_params[f"slot{i}"], x, cfg,
                                 positions, shared, enc, moe_groups)
            if st is not None:
                stats.append(st)
        return x, stats

    body = superblock
    if cfg.remat:
        body = jax.checkpoint(
            superblock, policy=jax.checkpoint_policies.nothing_saveable)
    x, stack_stats = jax.lax.scan(body, x, params["stack"])
    logits = _unembed(params, x, cfg)
    if not with_stats:
        return logits
    return logits, _moe_stats(prologue_stats, stack_stats, cfg)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> PyTree:
    """Decode cache pytree; stacked over superblock repetitions per slot."""
    cache: dict[str, Any] = {}
    if cfg.prologue:
        cache["prologue"] = [
            _block_init_cache(kind, cfg, batch, max_seq, dtype)
            for kind in cfg.prologue]

    def one_slot(kind):
        c = _block_init_cache(kind, cfg, batch, max_seq, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_super,) + a.shape), c)

    cache["stack"] = {f"slot{i}": one_slot(kind)
                      for i, kind in enumerate(cfg.superblock)}
    return cache


def cache_axes(cfg: ModelConfig) -> PyTree:
    """Logical axes for the cache (for sharding specs)."""
    def axes_for(kind, stacked: bool):
        lead = ("layers",) if stacked else ()
        if kind in ("attn", "attn_moe", "shared_attn"):
            a = ("batch", "cache_seq", "kv_heads", "head")
            return {"k": lead + a, "v": lead + a}
        if kind in ("mla", "mla_moe"):
            return {"ckv": lead + ("batch", "cache_seq", "kv_lora"),
                    "krope": lead + ("batch", "cache_seq", "head")}
        if kind == "cross_attn":
            a = ("batch", "enc_tokens", "kv_heads", "head")
            return {"ek": lead + a, "ev": lead + a}
        if kind == "mamba1":
            return {"conv": lead + ("batch", "conv", "ssm_inner"),
                    "h": lead + ("batch", "ssm_inner", "state")}
        if kind == "mamba2":
            return {"conv": lead + ("batch", "conv", "ssm_inner"),
                    "h": lead + ("batch", "ssm_heads", "head", "state")}
        raise ValueError(kind)

    axes: dict[str, Any] = {}
    if cfg.prologue:
        axes["prologue"] = [axes_for(k, False) for k in cfg.prologue]
    axes["stack"] = {f"slot{i}": axes_for(kind, True)
                     for i, kind in enumerate(cfg.superblock)}
    return axes


def decode_step(params, cache, tokens, pos, cfg: ModelConfig,
                moe_groups: int = 1) -> tuple[jax.Array, PyTree]:
    """One-token decode. tokens: (B,1) int32; pos: scalar int32 (current
    write position; all sequences share it -- continuous batching slots are
    handled by the serving layer). Returns (logits (B,1,V), new cache)."""
    x = _embed(params, tokens, cfg)
    shared = {"attn": params.get("shared_attn"),
              "mlp": params.get("shared_mlp")}

    new_cache: dict[str, Any] = {}
    if cfg.prologue:
        new_cache["prologue"] = []
        for i, kind in enumerate(cfg.prologue):
            x, c = _block_decode(kind, params["prologue"][i], x,
                                 cache["prologue"][i], cfg, pos, shared, moe_groups)
            new_cache["prologue"].append(c)

    def superblock(x, slot_in):
        slot_params, slot_cache = slot_in
        new_c = {}
        for i, kind in enumerate(cfg.superblock):
            x, c = _block_decode(kind, slot_params[f"slot{i}"], x,
                                 slot_cache[f"slot{i}"], cfg, pos, shared,
                                 moe_groups)
            new_c[f"slot{i}"] = c
        return x, new_c

    x, stack_cache = jax.lax.scan(superblock, x,
                                  (params["stack"], cache["stack"]))
    new_cache["stack"] = stack_cache
    logits = _unembed(params, x, cfg)
    return logits, new_cache


def loss_and_stats(params, batch, cfg: ModelConfig, moe_groups: int = 1):
    """(cross-entropy plus the MoE layers' balance losses, their stats)."""
    logits, stats = forward(params, batch["tokens"], cfg,
                            enc=batch.get("enc"), moe_groups=moe_groups,
                            with_stats=True)
    return cross_entropy_loss(logits, batch["labels"]) + stats["aux"], stats


def loss_fn(params, batch, cfg: ModelConfig, moe_groups: int = 1) -> jax.Array:
    return loss_and_stats(params, batch, cfg, moe_groups)[0]
