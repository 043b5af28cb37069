"""The plain DeepSeek-V2-Lite of `bench/problems/lm.py` against the
program's model, at small sizes on the CPU, in float32 on seeded random
weights.

The configuration dict of each test is the cell's own file with its
widths set to a registry config's small ones (`bench_cfg`, from
`data/lite_sizes.py`), so that the reference reads the same keys as on
the chip.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

CONFIG = harness.ROOT / "bench" / "configs" / \
    "deepseek_v2_lite_6l_ep8_pod4.json"
bench_cfg = harness.load_module(harness.ROOT, "tests",
                                "data/lite_sizes").bench_cfg
#: float32 program against the float32 reference: the sound gaps read
#: 1e-7 to 1e-6 (loss) here; each departure below moves the loss by 3e-4
#: or more
LIMIT = 1e-5

lm = harness.load_module(harness.ROOT, "problems", "lm")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model(variant="smoke_ep2", **changes):
    from repro.models import registry
    cfg = registry.get_config("deepseek-v2-lite", variant)
    return dataclasses.replace(cfg, dtype=jnp.float32, **changes)


def program_params(m, seed=3):
    from repro.models import transformer
    params, _ = transformer.init(jax.random.PRNGKey(seed), m)
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def tokens(m, batch=2, seq=32, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                           m.vocab_size)
    return t[:, :-1], t[:, 1:]


def program_loss(params, m, toks, labels):
    from repro.models import transformer
    return transformer.loss_fn(params, {"tokens": toks, "labels": labels}, m)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# -- the weights -------------------------------------------------------------


@pytest.mark.parametrize("variant", ["smoke_ep2", "smoke"])
def test_init_is_the_programs_leaf_by_leaf(variant):
    from repro.models import transformer
    m = model(variant)
    m = dataclasses.replace(m, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(11)
    mine = jax.tree_util.tree_leaves_with_path(lm.init(key, bench_cfg(m)))
    theirs = jax.tree_util.tree_leaves_with_path(transformer.init(key, m)[0])
    assert [p for p, _ in mine] == [p for p, _ in theirs]
    for (path, a), (_, b) in zip(mine, theirs):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert bool(jnp.array_equal(a, b)), path


def test_init_of_the_cell_has_the_programs_shapes():
    """At the cell's own sizes, shapes only (no weights are drawn)."""
    from repro.models import registry, transformer
    cfg = json.loads(CONFIG.read_text())
    m = registry.get_config(cfg["problem"]["params"]["arch"],
                            cfg["problem"]["params"]["variant"])
    key = jax.random.PRNGKey(0)
    mine = jax.eval_shape(lambda k: lm.init(k, cfg), key)
    theirs = jax.eval_shape(lambda k: transformer.init(k, m)[0], key)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
        mine, theirs)) == [True] * len(jax.tree.leaves(mine))


# -- the model ---------------------------------------------------------------


def test_loss_and_gradients_match_the_program():
    m = model()
    cfg = bench_cfg(m)
    params = program_params(m)
    toks, labels = tokens(m)
    want, want_g = jax.value_and_grad(program_loss)(params, m, toks, labels)
    got, got_g = jax.value_and_grad(
        lambda p: lm.loss(p, toks, labels, cfg, jnp.float32))(params)
    assert rel(got, want) < LIMIT
    gaps = jax.tree.leaves(jax.tree.map(rel, got_g, want_g))
    assert max(gaps) < 1e-4


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["query_chunks", "kv_streamed"])
def test_mla_without_q_lora_and_yarn_match_the_reference(streamed):
    """One MLA block at 2048 positions, where both of the program's long
    paths and the reference's query blocks run: the chunked queries, and
    under sharding rules the KV-streamed flash recurrence."""
    from repro.launch.mesh import make_mesh
    from repro.models import attention
    from repro.runtime import sharding as shrules
    m = model()
    assert m.mla_q_lora == 0 and m.rope_factor == 40.0
    s = lm.sizes(bench_cfg(m))
    prm = program_params(m)["prologue"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 2048, m.d_model))
    positions = jnp.arange(2048)[None]
    if streamed:
        with shrules.use_rules(shrules.DEFAULT_RULES,
                               make_mesh((1, 1, 1), ("pod", "data",
                                                     "model"))):
            got = attention.mla_apply(prm, x, m, positions)
    else:
        got = attention.mla_apply(prm, x, m, positions)
    want = lm._mla(prm, x, bench_cfg(m), s, False)
    assert rel(got, want) < 1e-5


def test_yarn_matches_the_published_rope_scaling():
    """The published config's YaRN: the frequencies, cos/sin factor 1
    (mscale = mscale_all_dim) and the softmax scale's mscale^2 = 1.590."""
    from repro.models import attention, common
    m = model("ep8")
    cfg = json.loads(CONFIG.read_text())
    inv_freq, cos_scale = lm._yarn(cfg, lm.sizes(cfg))
    np.testing.assert_allclose(np.asarray(common.yarn_freqs(64, m)),
                               np.asarray(inv_freq), rtol=1e-6)
    assert cos_scale == 1.0
    mscale2 = attention.mla_softmax_scale(m) * (128 + 64) ** 0.5
    assert abs(mscale2 - 1.590) < 1e-3
    # plain rope's frequencies at factor 1
    plain = dataclasses.replace(m, rope_factor=1.0)
    np.testing.assert_array_equal(np.asarray(common.yarn_freqs(64, plain)),
                                  np.asarray(common.rope_freqs(64, 1e4)))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert each: their routed parts, with the
    shared experts counted once, give the uncut layer, which is the
    reference's layer with all eight experts."""
    from repro.models import mlp
    whole = model("smoke")
    prm = program_params(whole)["stack"]["slot0"]["moe"]
    prm = jax.tree.map(lambda a: a[0], prm)  # the first MoE layer
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, whole.d_model))
    uncut, _ = mlp.moe_apply(prm, x, whole)
    h = mlp.rms_norm(x, prm["norm"])
    shared = mlp._ffn(prm["shared"], h, whole)
    routed = jnp.zeros_like(uncut)
    for i in range(whole.moe_experts):
        share = dataclasses.replace(whole, moe_experts=1,
                                    moe_experts_total=whole.moe_experts,
                                    moe_expert_offset=i)
        part = dict(prm, **{k: prm[k][i:i + 1]
                            for k in ("w_up", "w_gate", "w_down")})
        out, stats = mlp.moe_apply(part, x, share)
        assert int(stats["dropped"]) == 0
        routed = routed + (out - shared)
    assert rel(routed + shared, uncut) < 1e-5
    want, _ = lm._moe(prm, x, bench_cfg(whole), lm.sizes(bench_cfg(whole)),
                      False)
    assert rel(uncut, want) < 1e-5


def test_a_skewed_router_drops_nothing():
    """A router that scores every expert alike sends every token to the
    same top-k (the first k): the held experts take every assignment, and
    the layer still equals the reference's."""
    from repro.models import mlp
    m = model()
    prm = jax.tree.map(lambda a: a[0],
                       program_params(m)["stack"]["slot0"]["moe"])
    prm = dict(prm, router=jnp.zeros_like(prm["router"]))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, m.d_model))
    out, stats = mlp.moe_apply(prm, x, m)
    n = 2 * 32
    assert int(stats["dropped"]) == 0
    assert np.asarray(stats["expert_tokens"]).tolist() == \
        [n] * m.moe_top_k + [0] * (m.moe_experts - m.moe_top_k)
    want, _ = lm._moe(prm, x, bench_cfg(m), lm.sizes(bench_cfg(m)), False)
    assert rel(out, want) < 1e-5


def _leave_out_one_shared_expert(params, m):
    def cut(moe):
        F = m.moe_d_ff
        down = moe["shared"]["w_down"].at[..., F:, :].set(0.0)
        return dict(moe, shared=dict(moe["shared"], w_down=down))
    stack = params["stack"]["slot0"]
    return dict(params, stack={"slot0": dict(stack, moe=cut(stack["moe"]))})


def _without_mscale(m, params, monkeypatch):
    monkeypatch.setattr("repro.models.attention.mla_softmax_scale",
                        lambda c: (c.hd + c.mla_rope_head_dim) ** -0.5)
    return m, params


#: each: (model config, params, monkeypatch) -> the program's departed
#: (model config, params)
DEPARTURES = {
    "gates_renormalised": lambda m, p, _: (
        dataclasses.replace(m, moe_norm_topk=True), p),
    "yarn_mscale_left_out": _without_mscale,
    "shared_expert_left_out": lambda m, p, _: (
        m, _leave_out_one_shared_expert(p, m)),
    "held_slice_ignored": lambda m, p, _: (
        dataclasses.replace(m, moe_expert_offset=0), p),
    "balance_loss_left_out": lambda m, p, _: (
        dataclasses.replace(m, moe_seq_aux=0.0), p),
}


@pytest.mark.parametrize("departure", [None] + sorted(DEPARTURES))
def test_each_departure_breaks_the_limit(departure, monkeypatch):
    """The program's loss against the reference's: within LIMIT as it is,
    beyond it with any one part of the published layer left out. The
    share holds experts 4-7 of 8, so that ignoring its slice shows."""
    m = model(moe_expert_offset=4)
    cfg = bench_cfg(m)
    params = program_params(m)
    toks, labels = tokens(m)
    want = lm.loss(params, toks, labels, cfg, jnp.float32)
    if departure is not None:
        m, params = DEPARTURES[departure](m, params, monkeypatch)
    got = program_loss(params, m, toks, labels)
    gap = rel(got, want)
    if departure is None:
        assert gap < LIMIT
    else:
        assert gap > 3 * LIMIT, gap


def test_the_fp8_control_departs_from_the_reference():
    """The control (products through float8_e4m3fn) moves the loss by
    more than bfloat16 does, and its gradient flows."""
    from repro.models import transformer
    m = model()
    cfg = bench_cfg(m)
    params = program_params(m)
    toks, labels = tokens(m)
    ref = lm.loss(params, toks, labels, cfg, jnp.float32)
    ctl, g = jax.value_and_grad(
        lambda p: lm.loss(p, toks, labels, cfg, jnp.float8_e4m3fn))(params)
    bf = transformer.loss_fn(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        {"tokens": toks, "labels": labels},
        dataclasses.replace(m, dtype=jnp.bfloat16))
    assert rel(ctl, ref) > rel(bf, ref)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in jax.tree.leaves(g))
    assert sum(float(jnp.sum(jnp.abs(a))) for a in jax.tree.leaves(g)) > 0


def test_work_counts():
    """Model FLOPs of the cell: 0.717 GFLOP a token forward, three times
    that a trained token; the routed experts at K x 8 / 64 a token."""
    cfg = json.loads(CONFIG.read_text())
    per = lm.token_flops(cfg)
    assert abs(sum(per.values()) / 0.717e9 - 1) < 1e-3
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert per["experts"] == 5 * 6 * 8 / 64 * 6 * D * F
    tokens_ = 4 * 4096
    assert lm.step_flops(cfg) == 3 * tokens_ * sum(per.values())
    assert lm.step_flops(cfg, "experts") == 3 * tokens_ * per["experts"]


def _unwritten_ragged_dot():
    """`lax.ragged_dot` as the TPU's grouped matmul behaves: the rows
    past the groups are left unwritten, in the product and in the
    gradient of its left operand (here NaN)."""
    ragged_dot = jax.lax.ragged_dot

    def past(lhs, group_sizes):
        return (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]

    @jax.custom_vjp
    def unwritten(lhs, rhs, group_sizes):
        out = ragged_dot(lhs, rhs, group_sizes)
        return jnp.where(past(lhs, group_sizes), jnp.nan, out)

    def fwd(lhs, rhs, group_sizes):
        return unwritten(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda a, b: ragged_dot(a, b, group_sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        d_lhs = jnp.where(past(lhs, group_sizes), jnp.nan, d_lhs)
        return d_lhs, d_rhs, np.zeros(group_sizes.shape, jax.dtypes.float0)

    unwritten.defvjp(fwd, bwd)
    return unwritten


def test_rows_past_the_groups_reach_neither_loss_nor_gradient(monkeypatch):
    """With the grouped matmul leaving the rows past its groups unwritten
    (NaN), as the TPU's does, the loss and every gradient still match the
    reference's."""
    monkeypatch.setattr(jax.lax, "ragged_dot", _unwritten_ragged_dot())
    m = model()
    cfg = bench_cfg(m)
    params = program_params(m)
    toks, labels = tokens(m)
    want, want_g = jax.value_and_grad(
        lambda p: lm.loss(p, toks, labels, cfg, jnp.float32))(params)
    got, got_g = jax.value_and_grad(program_loss)(params, m, toks, labels)
    assert rel(got, want) < LIMIT
    assert max(jax.tree.leaves(jax.tree.map(rel, got_g, want_g))) < 1e-4
