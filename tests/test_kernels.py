"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret=True executes the kernel body in
Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref


KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("S,D,H,KH", [
    (128, 64, 4, 4),    # MHA
    (256, 64, 8, 2),    # GQA 4x
    (256, 128, 4, 1),   # MQA
    (512, 32, 2, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, D, H, KH, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, H, S, D), dtype)
    k = jax.random.normal(ks[1], (2, KH, S, D), dtype)
    v = jax.random.normal(ks[2], (2, KH, S, D), dtype)
    out = ops.flash_attention(q, k, v, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=atol, rtol=atol * 10)


def test_flash_attention_non_causal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = ops.flash_attention(q, k, v, causal=False, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("S,d,N", [(256, 128, 8), (512, 256, 16),
                                   (256, 512, 16)])
def test_selective_scan_sweep(S, d, N):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (2, S, d)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, d)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (d, N)) * 0.3)
    B = jax.random.normal(ks[3], (2, S, N)) * 0.5
    C = jax.random.normal(ks[4], (2, S, N)) * 0.5
    Dk = jnp.ones((d,))
    y = ops.selective_scan(x, dt, A, B, C, Dk, interpret=True)
    ye = ref.selective_scan_ref(x, dt, A, B, C, Dk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               atol=5e-4, rtol=2e-3)


@pytest.mark.parametrize("S,H,P,N,chunk", [
    (256, 4, 32, 16, 128), (512, 2, 64, 64, 128), (128, 8, 64, 32, 64)])
def test_ssd_scan_sweep(S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (2, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (2, S, N)) * 0.5
    C = jax.random.normal(ks[4], (2, S, N)) * 0.5
    from repro.kernels.ssd_scan import ssd_scan
    y = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    ye = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               atol=5e-4, rtol=2e-3)


def test_ssd_kernel_matches_model_mixer():
    """The Pallas SSD kernel agrees with the model's chunked XLA
    implementation (repro.models.ssm._ssd_chunk path)."""
    import dataclasses
    from repro.models import get_config
    from repro.models import ssm as ssm_mod
    cfg = get_config("zamba2-2.7b", "smoke")
    d_inner, nheads = ssm_mod._m2_dims(cfg)
    S = 64
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (2, S, nheads, cfg.ssm_head_dim)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, nheads)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (nheads,)) * 0.3)
    B = jax.random.normal(ks[3], (2, S, cfg.ssm_state)) * 0.5
    C = jax.random.normal(ks[0], (2, S, cfg.ssm_state)) * 0.5
    from repro.kernels.ssd_scan import ssd_scan
    y_kernel = ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    h0 = jnp.zeros((2, nheads, cfg.ssm_head_dim, cfg.ssm_state))
    _, y_model = ssm_mod._ssd_chunk(h0, x, dt, B, C, A)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=5e-4, rtol=2e-3)


@given(m=st.integers(1, 50000), k=st.integers(1, 6),
       sw=st.floats(0.05, 0.9))
@settings(max_examples=10)
def test_gossip_mix_hypothesis(m, k, sw):
    ks = jax.random.split(jax.random.PRNGKey(m % 97), 2)
    sb = jax.random.normal(ks[0], (m,), jnp.float32)
    nb = jax.random.normal(ks[1], (k, m), jnp.float32)
    ew = (1.0 - sw) / k
    out = ops.gossip_mix(sb, nb, sw, ew, interpret=True)
    expect = ref.gossip_mix_ref(sb, nb, sw, ew)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_gossip_mix_consensus_semantics():
    """gossip_mix(self, neighbors, 1/(k+1), 1/(k+1)) == one mixing round of
    the lazy uniform matrix restricted to received buffers."""
    from repro.core.graphs import ring_graph
    g = ring_graph(5)
    rng = np.random.default_rng(0)
    z = rng.normal(size=(5, 1000)).astype(np.float32)
    # node 0's neighbors on the ring are 1 and 4
    nbrs = jnp.asarray(z[[1, 4]])
    out = ops.gossip_mix(jnp.asarray(z[0]), nbrs, g.self_weight,
                         g.edge_weight, interpret=True)
    expect = (g.mixing_matrix() @ z)[0]
    np.testing.assert_allclose(np.asarray(out), expect, atol=1e-5)


# ---------------------------------------------------------------------------
# weighted (per-edge) gossip: kernel vs ref vs dense matmul
# ---------------------------------------------------------------------------


def _expander_S_in(g):
    return jnp.asarray(np.stack([np.asarray(p) for p in g.perms], axis=1))


@given(n8=st.integers(1, 5), m=st.integers(1, 3000), k=st.integers(1, 5))
@settings(max_examples=10)
def test_gossip_mix_weighted_kernel_vs_ref(n8, m, k):
    """The per-edge-weight Pallas kernel (interpret=True) against the jnp
    oracle, over unpadded shapes routed through the padding wrapper."""
    n = 8 * n8  # ops pads rows; vary the lane padding via m
    ks = jax.random.split(jax.random.PRNGKey(m % 89), 4)
    z = jax.random.normal(ks[0], (n, m), jnp.float32)
    S_in = jax.random.randint(ks[1], (n, k), 0, n)
    ws = jax.random.uniform(ks[2], (n,), jnp.float32, 0.05, 0.9)
    we = jax.random.uniform(ks[3], (n, k), jnp.float32, 0.0, 0.3)
    out = ops.gossip_gather_mix(z, S_in, ws, we, interpret=True,
                                use_kernel=True)
    expect = ref.gossip_gather_mix_ref(z, S_in, ws, we)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_gossip_gather_mix_uniform_matches_matmul(use_kernel):
    """Uniform lazy weights on a k-regular expander == P @ z, for both the
    kernel route and the fused-jnp (CPU fast path) route."""
    from repro.core.graphs import kregular_expander
    g = kregular_expander(12, k=4, seed=0)
    z = jax.random.normal(jax.random.PRNGKey(1), (12, 257), jnp.float32)
    out = ops.gossip_gather_mix(
        z, _expander_S_in(g), jnp.float32(g.self_weight),
        jnp.float32(g.edge_weight), interpret=True, use_kernel=use_kernel)
    expect = jnp.asarray(g.mixing_matrix(), jnp.float32) @ z
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_gossip_gather_mix_weighted_matches_matmul(use_kernel):
    """A reweighted edge-supported mixing matrix (the
    `AdaptiveController(reweight_gossip=True)` shape: arbitrary weights on
    diag + edges) folded into per-edge vectors == W @ z."""
    from repro.core.graphs import kregular_expander
    g = kregular_expander(12, k=4, seed=0)
    n = g.n
    rng = np.random.default_rng(3)
    S_in_np = np.stack([np.asarray(p) for p in g.perms], axis=1)
    W = np.diag(rng.uniform(0.2, 0.6, n))
    for i in range(n):
        for src in set(S_in_np[i]):
            W[i, src] = rng.uniform(0.05, 0.2)
    # slot weight = W[i, src] / multiplicity (engines' convention)
    mult = np.zeros_like(S_in_np)
    for j in range(S_in_np.shape[1]):
        mult[:, j] = (S_in_np == S_in_np[:, j][:, None]).sum(axis=1)
    we = (W[np.arange(n)[:, None], S_in_np] / mult).astype(np.float32)
    z = jax.random.normal(jax.random.PRNGKey(2), (n, 130), jnp.float32)
    out = ops.gossip_gather_mix(
        z, jnp.asarray(S_in_np), jnp.asarray(np.diag(W), jnp.float32),
        jnp.asarray(we), interpret=True, use_kernel=use_kernel)
    expect = jnp.asarray(W, jnp.float32) @ z
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# non-smooth subgradient (paper section V.B): kernel vs the jnp body
# ---------------------------------------------------------------------------


def _nonsmooth(n, M, d, seed=0):
    rng = np.random.default_rng(seed)
    offset = rng.normal(0.0, 1.5, (n, 1, 1, d))
    centers = jnp.asarray(rng.normal(offset, 0.3, (n, M, 2, d)), jnp.float32)
    x = jnp.asarray(0.9 * offset[:, 0, 0] + rng.normal(0.0, 0.5, (n, d)),
                    jnp.float32)
    return x, centers


@pytest.mark.parametrize("n,M,d,block", [
    (8, 3, 128, (8, 1)),      # odd M, one pair a step
    (16, 5, 256, None),       # block_shape's choice
    (32, 6, 384, (16, 2)),    # two node blocks, three pair blocks
    (10, 7, 128, (8, 7)),     # n = 10: a node block with padding rows
    (16, 30, 128, (8, 15)),   # the benchmark's M, two pair blocks
])
def test_nonsmooth_subgrad_kernel_vs_ref(n, M, d, block):
    """The Pallas kernel (interpret=True) against the jnp body, within
    float32 rounding of the summed distances."""
    from repro.kernels import nonsmooth_subgrad as nsg
    x, centers = _nonsmooth(n, M, d, seed=n + M)
    out = nsg.nonsmooth_subgrad(x, nsg.kernel_layout(centers), block=block,
                                interpret=True)
    expect = ref.nonsmooth_subgrad_ref(x, centers)
    assert out.shape == (n, d) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-5)


def test_nonsmooth_subgrad_ties_pick_piece_zero():
    """Where both pieces are equally far (c1 = 2x - c0, exact in small
    integers) the argmax rule keeps piece 0, in the kernel as in the jnp
    body: g = 2 sum_j (x - c0), not its negative."""
    from repro.kernels import nonsmooth_subgrad as nsg
    n, M, d = 8, 3, 128
    rng = np.random.default_rng(1)
    x = rng.integers(-4, 5, (n, d)).astype(np.float32)
    c0 = rng.integers(-4, 5, (n, M, d)).astype(np.float32)
    centers = jnp.asarray(np.stack([c0, 2 * x[:, None] - c0], axis=2))
    want = 2.0 * np.sum(x[:, None] - c0, axis=1)
    assert np.abs(want).max() > 0
    expect = ref.nonsmooth_subgrad_ref(jnp.asarray(x), centers)
    out = nsg.nonsmooth_subgrad(jnp.asarray(x), nsg.kernel_layout(centers),
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(expect), want)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_nonsmooth_subgrad_vmap_shares_the_centres():
    """Lanes of a `vmap` (the batched sweep's) each get their own
    subgradient, and the kernel's centres operand stays unbatched."""
    n, M, d, B = 8, 3, 128, 3
    _, centers = _nonsmooth(n, M, d)
    from repro.kernels import nonsmooth_subgrad as nsg
    ck = nsg.kernel_layout(centers)
    xs = jax.random.normal(jax.random.PRNGKey(3), (B, n, d), jnp.float32)

    def lane(x):
        return ops.nonsmooth_subgrad_impl(x, centers, ck, interpret=True,
                                          use_kernel=True)

    out = jax.vmap(lane)(xs)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out[b]),
            np.asarray(ref.nonsmooth_subgrad_ref(xs[b], centers)),
            atol=1e-4, rtol=1e-5)
    calls = [e for e in jax.make_jaxpr(jax.vmap(lane))(xs).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert [v.aval.shape for v in calls[0].invars] == [(B, n, d), ck.shape]


@pytest.mark.parametrize("d,on_tpu,kernel", [
    (256, False, False),   # the CPU: the jnp body
    (256, True, True),     # a TPU, d a multiple of 128: the kernel
    (200, True, False),    # a TPU, d not a multiple of 128: the jnp body
])
def test_nonsmooth_subgrad_dispatch(monkeypatch, d, on_tpu, kernel):
    """The platform and the width pick the path; the jnp path is the body
    itself, bit for bit."""
    x, centers = _nonsmooth(8, 3, d)
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    ck = ops.nonsmooth_kernel_layout(centers)
    assert (ck is not None) == kernel

    def sub(x):
        return ops.nonsmooth_subgrad_impl(x, centers, ck)

    jaxpr = str(jax.make_jaxpr(sub)(x))
    assert ("pallas_call" in jaxpr) == kernel
    if not on_tpu:
        np.testing.assert_array_equal(
            np.asarray(jax.jit(sub)(x)),
            np.asarray(jax.jit(ref.nonsmooth_subgrad_ref)(x, centers)))


# ---------------------------------------------------------------------------
# non-smooth objective (paper section V.B): split distances vs direct ones
# ---------------------------------------------------------------------------


def _objective_f64(x, centers):
    """F(x) in float64 numpy, the third opinion on both float32 forms."""
    diff = (np.asarray(x, np.float64)[None, None, None]
            - np.asarray(centers, np.float64))
    return float(np.mean(np.sum(np.max(np.sum(diff * diff, -1), -1), -1)))


def _split_objective(centers):
    rows = centers.reshape(-1, centers.shape[-1])
    centers_sq = jnp.sum(centers * centers, axis=-1)
    return jax.jit(lambda x: ops.nonsmooth_objective(x, rows, centers_sq))


def _assert_objective(got, x, centers, rtol=1e-6):
    """Within `rtol` of the direct-difference form and of float64."""
    want = _objective_f64(x, centers)
    direct = float(ref.nonsmooth_objective_ref(jnp.asarray(x), centers))
    assert abs(float(got) - direct) <= rtol * abs(direct)
    assert abs(float(got) - want) <= rtol * abs(want)


@pytest.mark.parametrize("n,M,d", [
    (1, 3, 128),      # one node
    (4, 5, 200),      # d not a multiple of 128
    (8, 30, 128),     # the benchmark's M
    (16, 7, 384),
    (10, 4, 1000),
])
def test_nonsmooth_objective_vs_ref(n, M, d):
    """The split form at one x and under `vmap` over the stack of node
    points, as the simulator's evaluation calls it."""
    xs, centers = _nonsmooth(n, M, d, seed=n * M)
    f = _split_objective(centers)
    _assert_objective(f(xs[0]), xs[0], centers)
    stacked = jax.jit(jax.vmap(f))(xs)
    assert stacked.shape == (n,) and stacked.dtype == jnp.float32
    for i in range(n):
        _assert_objective(stacked[i], xs[i], centers)


@pytest.mark.parametrize("n", [2, 16])
def test_nonsmooth_objective_large_norm(n):
    """x of large norm beside node 0's centres: there ||x||^2 is over a
    thousand times ||x - c||^2, so those pairs' split terms cancel; the
    other nodes' far pairs keep F itself of ||x||^2's size."""
    M, d = 6, 256
    rng = np.random.default_rng(n)
    _, centers = _nonsmooth(n, M, d, seed=n)
    shift = 30.0 * np.sign(rng.normal(size=d))
    c = np.asarray(centers).copy()
    c[0] = shift + rng.normal(0.0, 0.3, (M, 2, d))
    centers = jnp.asarray(c, jnp.float32)
    x = jnp.asarray(shift + rng.normal(0.0, 0.1, d), jnp.float32)
    near = np.sum((np.asarray(x) - c[0]) ** 2, axis=-1)
    assert float(jnp.sum(x * x)) > 1e3 * near.max()
    _assert_objective(_split_objective(centers)(x), x, centers)


@pytest.mark.parametrize("i,m,k", [(0, 0, 0), (3, 2, 1), (7, 5, 0)])
def test_nonsmooth_objective_on_a_centre(i, m, k):
    """x exactly on one centre of a pair: its own distance is 0 and the
    max takes the pair's other centre."""
    n, M, d = 8, 6, 256
    _, centers = _nonsmooth(n, M, d, seed=5)
    x = centers[i, m, k]
    _assert_objective(_split_objective(centers)(x), x, centers)


def test_nonsmooth_objective_cancellation_is_bounded_by_the_terms():
    """Where F is far below the size of its split terms -- one node, x
    among its own centres at a large norm -- the split loses digits in
    proportion to that ratio and no more: its error stays within float32
    rounding of M ||x||^2 + mean_i sum_j max_k ||c_ijk||^2."""
    M, d = 4, 256
    rng = np.random.default_rng(11)
    shift = 30.0 * np.sign(rng.normal(size=d))
    c = shift + rng.normal(0.0, 0.3, (1, M, 2, d))
    x = jnp.asarray(shift + rng.normal(0.0, 0.1, d), jnp.float32)
    centers = jnp.asarray(c, jnp.float32)
    c64 = np.asarray(centers, np.float64)
    scale = (M * float(np.sum(np.asarray(x, np.float64) ** 2))
             + float(np.sum(np.max(np.sum(c64 * c64, -1), -1))))
    want = _objective_f64(x, centers)
    assert scale > 1e3 * want
    got = float(_split_objective(centers)(x))
    assert abs(got - want) <= 1e-6 * scale


def test_nonsmooth_problem_objective_is_the_split_form():
    """The `nonsmooth` problem evaluates F by the split form, its cross
    terms one dot at "highest" precision, within 1e-6 of the direct form."""
    from repro.experiments.components import nonsmooth_centers, problems
    n, M, d, seed = 6, 4, 130, 3
    prob = problems.build("nonsmooth", n=n, M=M, d=d, seed=seed)
    centers = jnp.asarray(nonsmooth_centers(n, M, d, seed), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (d,), jnp.float32)
    _assert_objective(jax.jit(prob.objective)(x), x, centers)
    dots = [e for e in jax.make_jaxpr(prob.objective)(x).jaxpr.eqns
            if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert dots[0].params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
