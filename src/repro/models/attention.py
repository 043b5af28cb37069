"""Attention blocks: GQA self-attention, MLA (DeepSeek latent attention),
cross-attention (VLM), each with a prefill path and a KV-cache decode path.

All shapes follow (batch, seq, heads, head_dim). GQA repeats are expressed by
grouping q heads as (kv_heads, group) so the einsums contract natively
without materializing repeated K/V.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, apply_rope, barrier, p, pz,
                                 rms_norm, yarn_freqs, yarn_mscale)
from repro.runtime.sharding import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# GQA self-attention
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(key, 5)
    H, K, hd, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd, cfg.d_model
    prm = {
        "wq": p(ks[0], (D, H, hd), ("embed", "q_heads", "head"), cfg.dtype),
        "wk": p(ks[1], (D, K, hd), ("embed", "kv_heads", "head"), cfg.dtype),
        "wv": p(ks[2], (D, K, hd), ("embed", "kv_heads", "head"), cfg.dtype),
        "wo": p(ks[3], (H, hd, D), ("q_heads", "head", "embed"), cfg.dtype),
        "norm": pz((D,), ("embed",), jnp.float32),
    }
    if cfg.qkv_bias:
        prm["bq"] = pz((H, hd), ("q_heads", "head"), cfg.dtype)
        prm["bk"] = pz((K, hd), ("kv_heads", "head"), cfg.dtype)
        prm["bv"] = pz((K, hd), ("kv_heads", "head"), cfg.dtype)
    return prm


def _qkv(prm, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, prm["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, prm["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, prm["wv"])
    if cfg.qkv_bias:
        q, k, v = q + prm["bq"], k + prm["bk"], v + prm["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # q and the attention output stay sequence-parallel; ONLY k/v are
    # gathered across the model axis (kv heads are small - this replaces
    # re-gathering the full residual, a ~8x collective-byte cut measured in
    # EXPERIMENTS.md section Perf). The double constraint pins k/v to be
    # COMPUTED sequence-sharded and THEN gathered (bf16, small), preventing
    # GSPMD from hoisting the gather up to the fp32 residual.
    q = constrain(q, ("batch", "seq_sp", "q_heads", "head"))
    k = constrain(k, ("batch", "seq_sp", "kv_heads", "head"))
    v = constrain(v, ("batch", "seq_sp", "kv_heads", "head"))
    k = barrier(k)
    v = barrier(v)
    k = constrain(k, ("batch", None, "kv_heads", "head"))
    v = constrain(v, ("batch", None, "kv_heads", "head"))
    return q, k, v


_CHUNK_THRESHOLD = 1024
_Q_CHUNK = 256
_KV_CHUNK = 1024


def _sdpa_causal_streamed(q, k, v, scale):
    """Causal attention with the online-softmax (flash) recurrence over KV
    chunks, in plain XLA. q: (B,S,K,G-grouped H,hd); masks use GLOBAL row
    indices so the math is shard-layout independent."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    v_hd = v.shape[-1]
    qg = q.reshape(B, S, K, G, hd)
    nc = T // _KV_CHUNK
    ks = jnp.moveaxis(k.reshape(B, nc, _KV_CHUNK, K, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nc, _KV_CHUNK, K, v_hd), 1, 0)
    rows = jnp.arange(S) + (T - S)                        # global positions

    def chunk_fn(carry, inp):
        m, l, acc = carry                  # (B,S,K,G,1) x2, (B,S,K,G,v_hd)
        k_c, v_c, ci = inp
        s = jnp.einsum("bskgh,btkh->bskgt", qg, k_c).astype(jnp.float32)
        s = s * scale
        cols = ci * _KV_CHUNK + jnp.arange(_KV_CHUNK)
        mask = rows[:, None] >= cols[None, :]             # (S, chunk)
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bskgt,btkh->bskgh", p.astype(q.dtype), v_c)
        acc = acc * corr + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, S, K, G, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, K, G, 1), jnp.float32)
    acc0 = jnp.zeros((B, S, K, G, v_hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(chunk_fn), (m0, l0, acc0),
                                  (ks, vs, jnp.arange(nc)))
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out.reshape(B, S, H, v_hd)


def _sdpa_causal(q, k, v, cfg: ModelConfig, scale: float | None = None):
    """Grouped causal attention. q: (B,S,H,hd); k,v: (B,T,K,hd); the
    softmax scale defaults to 1/sqrt(hd).

    For long sequences the q dimension is processed in chunks under a
    rematerialized scan, so the (S x T) score matrix never materializes --
    the XLA-level analogue of flash attention (the Pallas kernel in
    repro/kernels is the TPU-tiled version; this path keeps cost_analysis
    exact for the dry-run and is the oracle in kernel tests)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    from repro.runtime.sharding import rules_active
    if rules_active() and T > _KV_CHUNK and T % _KV_CHUNK == 0:
        # production path: q rows stay sequence-parallel; stream the softmax
        # over KV chunks (flash recurrence in XLA) so the (S_loc x T) score
        # tensor never materializes. KV-chunking composes with seq_sp
        # sharding (q-chunking would slice the sharded dim).
        return _sdpa_causal_streamed(q, k, v, scale)
    if S <= _CHUNK_THRESHOLD or S % _Q_CHUNK != 0 or rules_active():
        qg = q.reshape(B, S, K, G, hd)
        scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
        scores = scores * scale
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        scores = jnp.where(mask, scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgst,btkh->bskgh", w, v).reshape(
            B, S, H, v.shape[-1])
        return out

    nc = S // _Q_CHUNK
    qs = jnp.moveaxis(
        q.reshape(B, nc, _Q_CHUNK, K, G, hd), 1, 0)       # (nc,B,c,K,G,hd)
    cols = jnp.arange(T)

    def chunk_fn(_, inp):
        qc, ci = inp                                      # (B,c,K,G,hd)
        scores = jnp.einsum("bskgh,btkh->bkgst", qc,
                            k).astype(jnp.float32) * scale
        rows = ci * _Q_CHUNK + jnp.arange(_Q_CHUNK) + (T - S)
        mask = rows[:, None] >= cols[None, :]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgst,btkh->bskgh", w, v)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(chunk_fn), None,
                           (qs, jnp.arange(nc)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, v.shape[-1])
    return out


def gqa_apply(prm, x, cfg: ModelConfig, positions) -> jax.Array:
    """Prefill/training forward (causal)."""
    h = rms_norm(x, prm["norm"])
    q, k, v = _qkv(prm, h, cfg, positions)
    out = _sdpa_causal(q, k, v, cfg)
    out = jnp.einsum("bshk,hkd->bsd", out, prm["wo"])
    return constrain(out, ("batch", "seq_sp", "embed_act"))


def gqa_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> PyTree:
    K, hd = cfg.num_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_seq, K, hd), dtype),
        "v": jnp.zeros((batch, max_seq, K, hd), dtype),
    }


def gqa_decode(prm, x, cache, cfg: ModelConfig, pos) -> tuple[jax.Array, PyTree]:
    """One-token decode. x: (B,1,D); pos: scalar current position; the cache
    is pre-allocated to max_seq and sequence-sharded for long contexts."""
    h = rms_norm(x, prm["norm"])
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q, k, v = _qkv(prm, h, cfg, positions)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
    ck = constrain(ck, ("batch", "cache_seq", "kv_heads", "head"))
    cv = constrain(cv, ("batch", "cache_seq", "kv_heads", "head"))
    B, _, H, hd = q.shape
    K = ck.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    # preferred_element_type runs the contraction bf16 x bf16 -> f32 WITHOUT
    # converting the cache operand (an .astype(f32) after the einsum made
    # XLA materialize an f32 copy of the whole layer-stacked cache: +8 GiB).
    scores = jnp.einsum("bkgh,btkh->bkgt", qg, ck,
                        preferred_element_type=jnp.float32)
    scores = constrain(scores, ("batch", "kv_heads", None, "cache_seq"))
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    T = ck.shape[1]
    valid = jnp.arange(T) <= pos
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    w = constrain(w, ("batch", "kv_heads", None, "cache_seq"))
    out = jnp.einsum("bkgt,btkh->bkgh", w, cv).reshape(B, 1, H, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, prm["wo"])
    out = constrain(out, ("batch", "seq", "embed_act"))
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA -- multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig) -> PyTree:
    """With `mla_q_lora` 0 (DeepSeek-V2-Lite) q is one projection `wq`
    from the residual, with no low-rank stage."""
    ks = jax.random.split(key, 8)
    D, H = cfg.d_model, cfg.num_heads
    qk_nope, rope_hd = cfg.hd, cfg.mla_rope_head_dim
    v_hd = cfg.mla_v_head_dim or cfg.hd
    kvl, ql = cfg.mla_kv_lora, cfg.mla_q_lora
    if ql:
        q = {"wq_a": p(ks[0], (D, ql), ("embed", "q_lora"), cfg.dtype),
             "q_norm": pz((ql,), ("q_lora",), jnp.float32),
             "wq_b": p(ks[1], (ql, H, qk_nope + rope_hd),
                       ("q_lora", "q_heads", "head"), cfg.dtype)}
    else:
        q = {"wq": p(ks[0], (D, H, qk_nope + rope_hd),
                     ("embed", "q_heads", "head"), cfg.dtype)}
    return {
        **q,
        "wkv_a": p(ks[2], (D, kvl + rope_hd), ("embed", "kv_lora"), cfg.dtype),
        "kv_norm": pz((kvl,), ("kv_lora",), jnp.float32),
        "wk_b": p(ks[3], (kvl, H, qk_nope), ("kv_lora", "q_heads", "head"),
                  cfg.dtype),
        "wv_b": p(ks[4], (kvl, H, v_hd), ("kv_lora", "q_heads", "head"),
                  cfg.dtype),
        "wo": p(ks[5], (H, v_hd, D), ("q_heads", "head", "embed"), cfg.dtype,
                scale=(H * v_hd) ** -0.5),
        "norm": pz((D,), ("embed",), jnp.float32),
    }


def _mla_rope(x, positions, cfg: ModelConfig):
    """Rope on the MLA's rope dims, YaRN-scaled when the config is."""
    return apply_rope(x, positions, cfg.rope_theta,
                      yarn_freqs(x.shape[-1], cfg))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(nope + rope dims), times YaRN's mscale(mscale_all_dim)
    squared, as DeepSeek-V2's attention sets it."""
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return m * m / float(cfg.hd + cfg.mla_rope_head_dim) ** 0.5


def _mla_q(prm, h, cfg: ModelConfig, positions):
    qk_nope = cfg.hd
    if "wq" in prm:
        q = jnp.einsum("bsd,dhk->bshk", h, prm["wq"])
    else:
        ql = jnp.einsum("bsd,dq->bsq", h, prm["wq_a"])
        ql = rms_norm(ql, prm["q_norm"])
        q = jnp.einsum("bsq,qhk->bshk", ql, prm["wq_b"])
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = _mla_rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _mla_kv_latent(prm, h, cfg: ModelConfig, positions):
    kvl = cfg.mla_kv_lora
    kv = jnp.einsum("bsd,dq->bsq", h, prm["wkv_a"])
    c_kv, k_rope = kv[..., :kvl], kv[..., kvl:]
    c_kv = rms_norm(c_kv, prm["kv_norm"])
    k_rope = _mla_rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0, :]
    return c_kv, k_rope


def mla_apply(prm, x, cfg: ModelConfig, positions) -> jax.Array:
    """Prefill: expand the latent per head, then run the shared (chunked)
    causal attention with the rope dims concatenated onto q/k. The softmax
    scale uses the combined qk dim (nope+rope) and YaRN's mscale, matching
    DeepSeek-V2 (`mla_softmax_scale`)."""
    h = rms_norm(x, prm["norm"])
    q_nope, q_rope = _mla_q(prm, h, cfg, positions)
    c_kv, k_rope = _mla_kv_latent(prm, h, cfg, positions)
    k_nope = jnp.einsum("bsq,qhk->bshk", c_kv, prm["wk_b"])
    v = jnp.einsum("bsq,qhk->bshk", c_kv, prm["wv_b"])
    B, S, H, _ = q_nope.shape
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, cfg.mla_rope_head_dim))], axis=-1)
    q_full = constrain(q_full, ("batch", "seq_sp", "q_heads", "head"))
    k_full = constrain(k_full, ("batch", "seq_sp", "q_heads", "head"))
    v = constrain(v, ("batch", "seq_sp", "q_heads", "head"))
    k_full = barrier(k_full)
    v = barrier(v)
    k_full = constrain(k_full, ("batch", None, "q_heads", "head"))
    v = constrain(v, ("batch", None, "q_heads", "head"))
    out = _sdpa_causal(q_full, k_full, v, cfg, mla_softmax_scale(cfg))
    out = jnp.einsum("bshk,hkd->bsd", out, prm["wo"])
    return constrain(out, ("batch", "seq_sp", "embed_act"))


def mla_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> PyTree:
    """MLA caches ONLY the compressed latent + shared rope key:
    (kv_lora + rope_hd) per token -- 576 dims for DeepSeek-V2 vs
    2*128*128=32768 for an equivalent dense MHA cache (57x smaller)."""
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.mla_kv_lora), dtype),
        "krope": jnp.zeros((batch, max_seq, cfg.mla_rope_head_dim), dtype),
    }


def mla_decode(prm, x, cache, cfg: ModelConfig, pos) -> tuple[jax.Array, PyTree]:
    """Absorbed decode: attention runs in the 512-dim latent space.
    q_absorbed = q_nope @ wk_b  (per head), scores = q_abs . c_kv -- the
    per-head K/V are never materialized (the MLA serving optimization)."""
    h = rms_norm(x, prm["norm"])
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(prm, h, cfg, positions)
    c_kv, k_rope = _mla_kv_latent(prm, h, cfg, positions)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], c_kv.astype(cache["ckv"].dtype), pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"], k_rope.astype(cache["krope"].dtype), pos, axis=1)
    ckv = constrain(ckv, ("batch", "cache_seq", "kv_lora"))
    krope = constrain(krope, ("batch", "cache_seq", "head"))
    # absorb W_uk:  (B,1,H,nope) x (kvl,H,nope) -> (B,H,kvl)
    q_abs = jnp.einsum("bshk,qhk->bhq", q_nope, prm["wk_b"])
    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bhq,btq->bht", q_abs, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshk,btk->bht", q_rope, krope,
                           preferred_element_type=jnp.float32))
    scores = scores * scale
    T = ckv.shape[1]
    valid = jnp.arange(T) <= pos
    scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bht,btq->bhq", w, ckv)           # latent context
    out = jnp.einsum("bhq,qhk->bhk", ctx, prm["wv_b"])  # expand V per head
    out = jnp.einsum("bhk,hkd->bd", out, prm["wo"])[:, None, :]
    out = constrain(out, ("batch", "seq", "embed_act"))
    return out, {"ckv": ckv, "krope": krope}


# ---------------------------------------------------------------------------
# Cross-attention (VLM decoder layers attending to stubbed vision tokens)
# ---------------------------------------------------------------------------


def cross_attn_init(key, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(key, 5)
    H, K, hd, D = cfg.num_heads, cfg.num_kv_heads, cfg.hd, cfg.d_model
    E = cfg.encoder_dim or D
    return {
        "wq": p(ks[0], (D, H, hd), ("embed", "q_heads", "head"), cfg.dtype),
        "wk": p(ks[1], (E, K, hd), ("enc_embed", "kv_heads", "head"), cfg.dtype),
        "wv": p(ks[2], (E, K, hd), ("enc_embed", "kv_heads", "head"), cfg.dtype),
        "wo": p(ks[3], (H, hd, D), ("q_heads", "head", "embed"), cfg.dtype),
        "norm": pz((D,), ("embed",), jnp.float32),
        "gate": pz((), (), jnp.float32),   # tanh-gated residual (llama3.2-V)
    }


_ENC_CHUNK = 1600


def cross_attn_apply(prm, x, enc, cfg: ModelConfig) -> jax.Array:
    """x: (B,S,D) decoder states; enc: (B,N,E) encoder tokens (no mask).

    q (and the output) stay sequence-parallel; the softmax over the N
    encoder tokens is STREAMED in chunks with a running (max, denom) -- the
    flash-attention recurrence in plain XLA -- so the (S x N) score tensor
    never materializes (it was a 100 GiB/device fp32 monster at the
    vision-90b train_4k cell; see EXPERIMENTS.md section Perf, iteration 3).
    """
    h = rms_norm(x, prm["norm"])
    # enc stays sharded over its token dim (model axis); k/v are projected
    # LOCALLY per enc shard and only the small k/v get gathered.
    enc = constrain(enc, ("batch", "enc_tokens", "enc_embed"))
    q = jnp.einsum("bsd,dhk->bshk", h, prm["wq"])
    q = constrain(q, ("batch", "seq_sp", "q_heads", "head"))
    k = jnp.einsum("bne,ehk->bnhk", enc, prm["wk"])
    v = jnp.einsum("bne,ehk->bnhk", enc, prm["wv"])
    k = constrain(k, ("batch", "enc_tokens", "kv_heads", "head"))
    v = constrain(v, ("batch", "enc_tokens", "kv_heads", "head"))
    k = barrier(k)
    v = barrier(v)
    k = constrain(k, ("batch", None, "kv_heads", "head"))
    v = constrain(v, ("batch", None, "kv_heads", "head"))
    B, S, H, hd = q.shape
    N, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    chunk = _ENC_CHUNK if (N % _ENC_CHUNK == 0 and N > _ENC_CHUNK) else N
    nc = N // chunk
    ks = jnp.moveaxis(k.reshape(B, nc, chunk, K, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nc, chunk, K, hd), 1, 0)

    def chunk_fn(carry, inp):
        m, l, acc = carry                   # (B,S,K,G,1) x2, (B,S,K,G,hd)
        k_c, v_c = inp                      # (B,chunk,K,hd)
        s = jnp.einsum("bskgh,bnkh->bskgn", qg, k_c).astype(jnp.float32)
        s = s * scale
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bskgn,bnkh->bskgh", p.astype(x.dtype), v_c)
        acc = acc * corr + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, S, K, G, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, K, G, 1), jnp.float32)
    acc0 = jnp.zeros((B, S, K, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(chunk_fn), (m0, l0, acc0),
                                  (ks, vs))
    out = (acc / jnp.maximum(l, 1e-30)).astype(x.dtype)
    out = out.reshape(B, S, H, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, prm["wo"])
    gate = prm["gate"]
    out = jnp.tanh(gate.astype(jnp.float32)).astype(x.dtype) * out
    return constrain(out, ("batch", "seq_sp", "embed_act"))
