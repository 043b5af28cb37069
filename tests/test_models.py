"""Per-architecture smoke tests (reduced configs, CPU): forward/train step
shape + finiteness, and decode-vs-forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ARCH_IDS, get_config, get_shapes, transformer
from repro.models.common import cross_entropy_loss


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, key):
    cfg = get_config(arch, "smoke")
    params, axes = transformer.init(key, cfg)
    B, S = 2, 16
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        batch["enc"] = jnp.ones((B, cfg.num_encoder_tokens, cfg.encoder_dim),
                                cfg.dtype)
    logits = transformer.forward(params, tokens, cfg, enc=batch.get("enc"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    loss, grads = jax.value_and_grad(transformer.loss_fn)(params, batch, cfg)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_shapes_assigned(arch):
    shapes = get_shapes(arch)
    assert set(shapes) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    cfg = get_config(arch, "full")
    long_cell = shapes["long_500k"]
    if cfg.supports_long_context:
        assert long_cell.skip is None
    else:
        assert long_cell.skip  # skip documented for full-attention archs


@pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v2-236b",
                                  "falcon-mamba-7b", "zamba2-2.7b",
                                  "llama-3.2-vision-90b"])
def test_decode_matches_forward(arch, key):
    """Token-by-token decode must reproduce the teacher-forced forward
    logits (same prefix) -- validates every cache implementation (GQA, MLA,
    conv+SSM states, cross-attn, shared-attn)."""
    import dataclasses
    cfg = get_config(arch, "smoke")
    if cfg.moe_experts:
        # capacity-dropping differs between batch prefill and per-token
        # decode by design; use a drop-free capacity for the equivalence
        cfg = dataclasses.replace(cfg, moe_capacity_factor=float(
            cfg.moe_experts))
    params, _ = transformer.init(key, cfg)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                                cfg.vocab_size)
    enc = None
    if cfg.family == "vlm":
        enc = jax.random.normal(
            key, (B, cfg.num_encoder_tokens, cfg.encoder_dim)).astype(cfg.dtype)
    full_logits = transformer.forward(params, tokens, cfg, enc=enc)

    cache = transformer.init_cache(cfg, B, S, jnp.float32)
    if cfg.family == "vlm":
        cache = _prefill_cross_cache(params, cache, cfg, enc)
    outs = []
    for pos in range(S):
        lg, cache = transformer.decode_step(params, cache,
                                            tokens[:, pos:pos + 1],
                                            jnp.int32(pos), cfg)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits, np.float32), atol=0.13, rtol=0.1)


def _prefill_cross_cache(params, cache, cfg, enc):
    """Fill cross-attention encoder K/V (normally done at prefill)."""
    from repro.models import transformer as T

    def fill(slot_params_stacked, slot_cache, kind):
        if kind != "cross_attn":
            return slot_cache
        def one(prm, c):
            k = jnp.einsum("bne,ehk->bnhk", enc, prm["attn"]["wk"])
            v = jnp.einsum("bne,ehk->bnhk", enc, prm["attn"]["wv"])
            return {"ek": k.astype(c["ek"].dtype),
                    "ev": v.astype(c["ev"].dtype)}
        return jax.vmap(one)(slot_params_stacked, slot_cache)

    new_stack = {}
    for i, kind in enumerate(cfg.superblock):
        new_stack[f"slot{i}"] = fill(params["stack"][f"slot{i}"],
                                     cache["stack"][f"slot{i}"], kind)
    cache = dict(cache)
    cache["stack"] = new_stack
    return cache


def test_cross_entropy_masking():
    logits = jnp.zeros((1, 4, 10))
    labels = jnp.array([[1, 2, -1, -1]])
    loss = cross_entropy_loss(logits, labels)
    assert np.isclose(float(loss), np.log(10), rtol=1e-5)


def test_moe_capacity_overflow_drops_gracefully():
    """With capacity_factor << 1 most assignments drop; output stays finite
    (dropped tokens contribute zero, not NaN)."""
    import dataclasses
    cfg = dataclasses.replace(get_config("llama4-maverick-400b-a17b",
                                         "smoke"), moe_capacity_factor=0.05)
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits = transformer.forward(params, tokens, cfg)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_param_counts_match_published():
    import math
    expected = {"llama3-8b": 8.0e9, "qwen1.5-110b": 111e9,
                "deepseek-v2-236b": 236e9,
                "llama4-maverick-400b-a17b": 400e9,
                "falcon-mamba-7b": 7.3e9,
                "deepseek-v2-lite": 15.7e9}
    for arch, want in expected.items():
        cfg = get_config(arch, "full")
        box = []

        def build(k, cfg=cfg):
            p, _ = transformer.init(k, cfg)
            return p

        tree = jax.eval_shape(build, jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
        assert abs(n - want) / want < 0.06, (arch, n, want)
