"""Shared model-definition machinery: configs, param construction with
logical sharding axes, norms, rotary embeddings, activations.

Every parameter is built through `p(key, shape, axes)` which returns a
`(array, axes)` pair; `split_axes` separates the two parallel trees. The
logical axis names are mapped to mesh axes by `launch/sharding.py` rules, so
the model code never mentions mesh axes directly.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

PyTree = Any

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config describes any of the supported families.

    The layer stack is `prologue` blocks followed by `n_super` repetitions of
    `superblock`. Block kinds:
      "attn"        self-attention (GQA/RoPE) + MLP
      "attn_moe"    self-attention + MoE FFN
      "mla"         multi-head latent attention (DeepSeek) + MLP
      "mla_moe"     MLA + MoE FFN
      "cross_attn"  cross-attention to encoder states + MLP (VLM)
      "mamba1"      Mamba-1 selective-scan block (attn-free)
      "mamba2"      Mamba-2 SSD block
      "shared_attn" the hybrid's weight-shared attention block (zamba2)
    """

    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    d_model: int
    vocab_size: int
    superblock: tuple[str, ...]
    n_super: int
    prologue: tuple[str, ...] = ()
    # attention
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0                # 0 -> d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 500000.0
    # YaRN rope scaling (the `rope_scaling` block of a published config);
    # a factor of 1 is plain rope. cos and sin are not scaled: YaRN scales
    # them by mscale / mscale_all_dim, 1 where the two are equal, as in
    # every DeepSeek-V2 config
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_original_max_positions: int = 4096
    # mlp
    d_ff: int = 0
    mlp_act: str = "swiglu"          # swiglu | squared_relu | gelu
    # Megatron TP-MLP (shard d_ff over 'model', gather/reduce the residual)
    # instead of the default pure sequence-parallel MLP. Preferable when the
    # per-layer weight bytes (3*D*F) exceed the microbatch activation bytes
    # (2*B_mb*S*D) -- i.e. very wide FFNs (see EXPERIMENTS.md section Perf).
    mlp_tp: bool = False
    # moe
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_shared: int = 0
    moe_d_ff: int = 0                # expert hidden size (defaults to d_ff)
    # > 0: experts sharded over the mesh's 'model' axis with a fixed
    # per-expert capacity (drops overflow); 0: the dropless held-expert
    # layer (grouped matmul over the tokens routed to the held experts)
    moe_capacity_factor: float = 1.25
    # the router's width when this config holds only a share of the
    # experts (0 -> moe_experts, all of them); the share is the contiguous
    # slice [moe_expert_offset, moe_expert_offset + moe_experts)
    moe_experts_total: int = 0
    moe_expert_offset: int = 0
    moe_norm_topk: bool = True       # renormalise the top-k gates to sum 1
    moe_routed_scale: float = 1.0    # routed_scaling_factor (unnormalised)
    moe_seq_aux: float = 0.0         # sequence-wise balance loss alpha
    # mla
    mla_kv_lora: int = 0
    mla_q_lora: int = 0
    mla_rope_head_dim: int = 64
    mla_v_head_dim: int = 0          # 0 -> head_dim
    # ssm
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64           # mamba2
    # hybrid / vlm / audio frontends
    shared_attn_lora: int = 64       # zamba2 per-invocation LoRA rank
    num_encoder_tokens: int = 0      # VLM: vision tokens; audio: frame count
    encoder_dim: int = 0             # stubbed frontend embedding dim
    # training
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    remat: bool = True
    # gradient-accumulation factor for the production train step (splits the
    # global batch; sized per arch so activations fit v5e HBM)
    train_microbatches: int = 1
    # bf16 Adam moments halve optimizer HBM (used by the 400B-class configs
    # where fp32 state alone exceeds the budget; updates stay fp32)
    opt_moments_bf16: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def router_width(self) -> int:
        return self.moe_experts_total or self.moe_experts

    @property
    def num_layers(self) -> int:
        return len(self.prologue) + self.n_super * len(self.superblock)

    @property
    def blocks(self) -> tuple[str, ...]:
        return self.prologue + self.superblock * self.n_super

    def has_block(self, kind_prefix: str) -> bool:
        return any(b.startswith(kind_prefix) for b in self.blocks)

    @property
    def is_attention_free(self) -> bool:
        return not any(
            b in ("attn", "attn_moe", "mla", "mla_moe", "cross_attn",
                  "shared_attn") for b in self.blocks)

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic memory path: SSM and hybrid families only."""
        return self.family in ("ssm", "hybrid")


# ---------------------------------------------------------------------------
# Params with logical axes
# ---------------------------------------------------------------------------


def p(key, shape: Sequence[int], axes: tuple[str | None, ...],
      dtype=jnp.bfloat16, scale: float | None = None):
    """Build one parameter leaf: (truncated-normal array, logical axes)."""
    assert len(shape) == len(axes), (shape, axes)
    if scale is None:
        fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    arr = scale * jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape),
                                              jnp.float32)
    return arr.astype(dtype), axes


def pz(shape: Sequence[int], axes: tuple[str | None, ...], dtype=jnp.bfloat16,
       fill: float = 0.0):
    """Constant-initialized parameter (biases, norm scales)."""
    assert len(shape) == len(axes), (shape, axes)
    return jnp.full(tuple(shape), fill, dtype), axes


def is_param_pair(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[1], tuple)
            and all(isinstance(a, (str, type(None))) for a in x[1]))


def split_axes(tree: PyTree) -> tuple[PyTree, PyTree]:
    """Split a tree of (array, axes) pairs into (arrays, axes) trees."""
    arrays = jax.tree.map(lambda x: x[0], tree, is_leaf=is_param_pair)
    axes = jax.tree.map(lambda x: x[1], tree, is_leaf=is_param_pair)
    return arrays, axes


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


#: identity that XLA may not optimize across: pins layouts/carry dtypes (see
#: call sites in models/attention.py, transformer.py)
barrier = jax.lax.optimization_barrier


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, cfg: ModelConfig) -> jax.Array:
    """YaRN (arXiv:2309.00071) rope frequencies: the extrapolated ones
    (theta^(-2i/d)) for the dimensions that turn more than beta_fast times
    over the original context, the interpolated ones (divided by the
    factor) for those that turn fewer than beta_slow times, and a linear
    blend between, as DeepSeek-V2's `DeepseekV2YarnRotaryEmbedding`."""
    base = rope_freqs(head_dim, cfg.rope_theta)
    if cfg.rope_factor <= 1.0:
        return base

    def dim_of(rotations):
        return (head_dim * math.log(cfg.rope_original_max_positions
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(dim_of(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.rope_beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    return base / cfg.rope_factor * ramp + base * extrapolate


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq). `freqs`
    (head_dim/2,) replaces the plain theta^(-2i/d) (YaRN's, `yarn_freqs`)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    angles = angles[..., None, :]                        # (..., s, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "squared_relu":
        r = jnp.maximum(x, 0.0)
        return r * r
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"activation {kind} handled in mlp (swiglu) or unknown")


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy in fp32; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
