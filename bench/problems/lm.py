"""Plain DeepSeek-V2-Lite, the model a `launch` training cell checks the
program against (with `bench/train_ref.py`'s consensus training loop).

Written from the published modeling code (`modeling_deepseek.py` of
deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434) and the configuration's
own keys, in straightforward jax.numpy; it imports nothing of the program
under test. Every block: x + MLA(RMSNorm(x)), then x + FFN(RMSNorm(x)),
the first `first_k_dense_replace` FFNs dense SwiGLU, the rest MoE.

- MLA with no q-LoRA (`q_lora_rank` null): q = h Wq per head, split into
  nope and rope dims; the kv latent c = RMSNorm(h Wkv_a[:512]) with a
  shared rope key h Wkv_a[512:]; k_nope = c Wk_b, v = c Wv_b; scores over
  the concatenated dims at softmax scale (nope + rope)^-1/2 times YaRN's
  mscale(mscale_all_dim)^2, causal.
- YaRN rope from the `rope_scaling` block: the extrapolated frequencies
  blended into the interpolated ones (factor 40) by a linear ramp
  between the dimensions that turn beta_fast and beta_slow times over the
  original 4096 positions; cos and sin times mscale / mscale_all_dim.
- MoE: softmax scores over all `router_experts` router outputs, greedy
  top-k, gates not renormalised (`norm_topk_prob` false) and times
  `routed_scaling_factor`; this chip's share computes only its held
  experts [offset, offset + n_routed_experts), here densely: each held
  expert over every token, weighted by its gate (0 where unrouted); the
  shared experts (width n_shared_experts x moe_intermediate_size) always.
- The sequence-wise balance loss (`seq_aux`), alpha x sum_i f_i P_i per
  sequence over all router outputs, averaged over sequences and summed
  over the MoE layers, is added to the mean cross-entropy.

Departures from the published code, none of which changes the function
family the weights span:

- rope rotates halves (x1, x2) of the rope dims, where the published code
  first de-interleaves them: under random weights that is a fixed
  permutation of the rope columns of Wq and Wkv_a;
- RMSNorm multiplies by (1 + w) with w drawn as 0, where the published
  weight is drawn as 1;
- the vocabulary and the experts are the configuration's share
  (`deployment`), as the program holds them;
- the weights are the program's draw, written out (`init`), so that one
  seed gives both the same weights.

`loss` runs in float32 (the reference) or with every matrix product's
operands rounded through `float8_e4m3fn`, each tensor scaled to the
format's range first, with gradients passed straight through (the
control, `dtype` float8_e4m3fn). Memory: each layer, each held expert
and each block of queries is rematerialised, so that one replica, its
float32 gradients and AdamW's moments fit one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.train_ref import (NUMBERS, readings,  # noqa: F401
                             reference_trace, spec_problem)

F32 = jnp.float32
#: queries per block of the attention
Q_BLOCK = 512
#: the largest float8_e4m3fn value
F8_MAX = 448.0


def sizes(cfg: dict) -> dict:
    """The model's sizes from the configuration's keys."""
    dep = cfg["deployment"]
    return {
        "D": cfg["hidden_size"], "V": cfg["vocab_size"],
        "H": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "kvl": cfg["kv_lora_rank"], "F_dense": cfg["intermediate_size"],
        "F": cfg["moe_intermediate_size"], "E": cfg["n_routed_experts"],
        "E_all": dep["router_experts"], "offset": dep["held_experts_from"],
        "K": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
        "alpha": cfg["aux_loss_alpha"] if cfg["seq_aux"] else 0.0,
    }


# ---------------------------------------------------------------------------
# The program's draw of the weights, written out
# ---------------------------------------------------------------------------


def _normal(key, shape, dtype, scale=None):
    """A truncated normal on [-2, 2], times 1/sqrt(shape[0]) by default."""
    if scale is None:
        scale = 1.0 / math.sqrt(max(shape[0] if len(shape) >= 2
                                    else shape[-1], 1))
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
            ).astype(dtype)


def _mla_init(key, s):
    ks = jax.random.split(key, 8)
    D, H = s["D"], s["H"]
    bf = jnp.bfloat16
    return {
        "wq": _normal(ks[0], (D, H, s["nope"] + s["rope"]), bf),
        "wkv_a": _normal(ks[2], (D, s["kvl"] + s["rope"]), bf),
        "kv_norm": jnp.zeros((s["kvl"],), F32),
        "wk_b": _normal(ks[3], (s["kvl"], H, s["nope"]), bf),
        "wv_b": _normal(ks[4], (s["kvl"], H, s["v"]), bf),
        "wo": _normal(ks[5], (H, s["v"], D), bf, (H * s["v"]) ** -0.5),
        "norm": jnp.zeros((D,), F32),
    }


def _ffn_init(key, D, F):
    ks = jax.random.split(key, 4)
    bf = jnp.bfloat16
    return {"w_up": _normal(ks[0], (D, F), bf),
            "w_down": _normal(ks[1], (F, D), bf),
            "w_gate": _normal(ks[2], (D, F), bf)}


def _moe_init(key, s):
    ks = jax.random.split(key, 6)
    D, E, F = s["D"], s["E"], s["F"]
    bf = jnp.bfloat16
    shared = _ffn_init(ks[4], D, F * s["shared"])
    return {"norm": jnp.zeros((D,), F32),
            "router": _normal(ks[0], (D, s["E_all"]), F32),
            "w_up": _normal(ks[1], (E, D, F), bf, D ** -0.5),
            "w_gate": _normal(ks[2], (E, D, F), bf, D ** -0.5),
            "w_down": _normal(ks[3], (E, F, D), bf, F ** -0.5),
            "shared": shared}


def _block_init(key, s, dense: bool):
    k1, k2 = jax.random.split(key)
    if dense:
        mlp = {"norm": jnp.zeros((s["D"],), F32),
               **_ffn_init(k2, s["D"], s["F_dense"])}
        return {"attn": _mla_init(k1, s), "mlp": mlp}
    return {"attn": _mla_init(k1, s), "moe": _moe_init(k2, s)}


def init(key, cfg: dict) -> dict:
    """The weights the program draws from `key`: the embedding and head,
    the leading dense blocks, and the MoE blocks stacked over depth."""
    s = sizes(cfg)
    keys = jax.random.split(key, 8)
    dense_keys = jax.random.split(keys[2], s["dense"])
    n_moe = s["layers"] - s["dense"]
    return {
        "embed": _normal(keys[0], (s["V"], s["D"]), jnp.bfloat16, 1.0),
        "final_norm": jnp.zeros((s["D"],), F32),
        "lm_head": _normal(keys[1], (s["D"], s["V"]), jnp.bfloat16),
        "prologue": [_block_init(dense_keys[i], s, True)
                     for i in range(s["dense"])],
        "stack": {"slot0": jax.vmap(
            lambda j: _block_init(jax.random.fold_in(keys[4], j), s, False))(
            jnp.arange(n_moe))},
    }


# ---------------------------------------------------------------------------
# The forward pass and the loss
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _round_f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


_round_f8.defvjp(lambda x: (_round_f8(x), None), lambda _, g: (g,))


def _mm(spec, a, b, f8):
    """A matrix product in float32, its operands rounded through
    float8_e4m3fn when `f8`."""
    if f8:
        a, b = _round_f8(a), _round_f8(b)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _yarn(cfg: dict, s: dict):
    """(inverse frequencies of the rope dims, the factor on cos and sin)."""
    y = cfg["rope_scaling"]
    d, theta, factor = s["rope"], s["theta"], y["factor"]
    extra = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    inter = extra / factor

    def dim_of(rotations):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = inter * (1.0 - mask) + extra * mask
    return inv_freq, _mscale(factor, y["mscale"]) / _mscale(
        factor, y["mscale_all_dim"])


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, inv_freq, cos_scale):
    """x: (B, S, ..., d); the halves rotated at positions 0 .. S-1."""
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq
    angles = angles.reshape((x.shape[1],) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(angles) * cos_scale, jnp.sin(angles) * cos_scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(p, x, cfg, s, f8):
    """Attention of a batch of sequences. x: (B, S, D)."""
    B, S, _ = x.shape
    h = _rms(x, p["norm"], s["eps"])
    q = _mm("bsd,dhk->bshk", h, p["wq"], f8)
    q_nope, q_pe = q[..., :s["nope"]], q[..., s["nope"]:]
    kv = _mm("bsd,dc->bsc", h, p["wkv_a"], f8)
    c = _rms(kv[..., :s["kvl"]], p["kv_norm"], s["eps"])
    k_pe = kv[..., s["kvl"]:]
    inv_freq, cos_scale = _yarn(cfg, s)
    q_pe = _rope(q_pe, inv_freq, cos_scale)
    k_pe = _rope(k_pe, inv_freq, cos_scale)
    k_nope = _mm("bsc,chk->bshk", c, p["wk_b"], f8)
    v = _mm("bsc,chk->bshk", c, p["wv_b"], f8)
    m = _mscale(cfg["rope_scaling"]["factor"],
                cfg["rope_scaling"]["mscale_all_dim"])
    scale = (s["nope"] + s["rope"]) ** -0.5 * m * m

    @jax.checkpoint
    def block(args):
        qn, qp, rows = args
        scores = (_mm("bqhk,bthk->bhqt", qn, k_nope, f8)
                  + _mm("bqhk,btk->bhqt", qp, k_pe, f8)) * scale
        causal = rows[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        return _mm("bhqt,bthk->bqhk", w, v, f8)

    nb = max(S // Q_BLOCK, 1)

    def split(a):  # (B, S, ...) -> (blocks, B, S / blocks, ...)
        return jnp.moveaxis(a.reshape((B, nb, S // nb) + a.shape[2:]), 1, 0)

    out = jax.lax.map(block, (split(q_nope), split(q_pe),
                              jnp.arange(S).reshape(nb, S // nb)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, s["H"], s["v"])
    return _mm("bshk,hkd->bsd", out, p["wo"], f8)


def _swiglu(p, h, f8):
    up = _mm("bsd,df->bsf", h, p["w_up"], f8)
    gate = _mm("bsd,df->bsf", h, p["w_gate"], f8)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"], f8)


def _moe(p, x, cfg, s, f8):
    """The MoE FFN of a batch of sequences and its balance loss, averaged
    over the sequences. x: (B, S, D)."""
    B, S, _ = x.shape
    h = _rms(x, p["norm"], s["eps"])
    scores = jax.nn.softmax(_mm("bsd,de->bse", h, p["router"], f8), axis=-1)
    gates, ids = jax.lax.top_k(scores, s["K"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    else:
        gates = gates * cfg["routed_scaling_factor"]

    @jax.checkpoint
    def expert(out, e):
        """Held expert e over every token, weighted by its gate."""
        weight = jnp.sum(jnp.where(ids == s["offset"] + e, gates, 0.0), -1)
        w = {name: p[name][e] for name in ("w_up", "w_gate", "w_down")}
        return out + _swiglu(w, h, f8) * weight[..., None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(s["E"]))
    out = out + _swiglu(p["shared"], h, f8)
    counts = jnp.sum(jax.nn.one_hot(ids, s["E_all"], dtype=F32), (1, 2))
    f = counts * s["E_all"] / (s["K"] * S)
    aux = s["alpha"] * jnp.mean(jnp.sum(f * jnp.mean(scores, axis=1), -1))
    return out, aux


def loss(params, tokens, labels, cfg: dict, dtype):
    """Mean token cross-entropy over the batch plus the balance losses,
    from float32 `params`: in float32, or with fp8-rounded products when
    `dtype` is float8_e4m3fn. tokens, labels: (B, S)."""
    s = sizes(cfg)
    f8 = jnp.dtype(dtype) == jnp.dtype(jnp.float8_e4m3fn)
    params = jax.tree.map(lambda a: a.astype(F32), params)
    x = params["embed"][tokens]

    def dense_block(p, x):
        x = x + _mla(p["attn"], x, cfg, s, f8)
        return x + _swiglu(p["mlp"], _rms(x, p["mlp"]["norm"], s["eps"]), f8)

    def moe_block(x, p):
        x = x + _mla(p["attn"], x, cfg, s, f8)
        out, aux = _moe(p["moe"], x, cfg, s, f8)
        return x + out, aux

    for p in params["prologue"]:
        x = jax.checkpoint(dense_block)(p, x)
    x, aux = jax.lax.scan(jax.checkpoint(moe_block), x,
                          params["stack"]["slot0"])
    h = _rms(x, params["final_norm"], s["eps"])
    logits = _mm("bsd,dv->bsv", h, params["lm_head"], f8)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold) + jnp.sum(aux)


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------


def token_flops(cfg: dict) -> dict:
    """Forward FLOPs per token, by layer: MLA (projections, and the
    attention over the causal half of the sequence), the dense FFN, the
    MoE (router, shared experts, and the routed experts at their expected
    share: top-k x held / router experts a token) and the head."""
    s = sizes(cfg)
    D, H = s["D"], s["H"]
    S = cfg["problem"]["params"]["seq_len"]
    qk = s["nope"] + s["rope"]
    keys = (S + 1) / 2
    mla = 2 * (D * H * qk + D * (s["kvl"] + s["rope"])
               + s["kvl"] * H * (s["nope"] + s["v"]) + H * s["v"] * D) \
        + 2 * H * keys * (qk + s["v"])
    experts = s["K"] * s["E"] / s["E_all"] * 6 * D * s["F"]
    n_moe = s["layers"] - s["dense"]
    return {
        "mla": s["layers"] * mla,
        "dense": s["dense"] * 6 * D * s["F_dense"],
        "experts": n_moe * experts,
        "moe_rest": n_moe * (2 * D * s["E_all"]
                             + 6 * D * s["F"] * s["shared"]),
        "head": 2 * D * s["V"],
    }


def step_flops(cfg: dict, part: str | None = None) -> float:
    """Model FLOPs of one replica's training step (forward and backward,
    three times the forward; rematerialisation not counted): the whole
    model, or the one `token_flops` part named."""
    per_token = token_flops(cfg)
    p = cfg["problem"]["params"]
    tokens = p["batch_per_node"] * p["seq_len"]
    value = per_token[part] if part else sum(per_token.values())
    return 3.0 * tokens * value
