"""repro.compress: compressor invariants (property-tested), the fused
compress-mix kernel vs the dense-matmul oracle, error-feedback
telescoping, netsim engine bit-identity under compression, and the
spec/tradeoff threading of the wire ratio c."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st

from repro.compress import (COMPRESSORS, INDEX_BYTES, VALUE_BYTES, Int8,
                            NoCompression, RandK, TopK, build_compressor,
                            compressors, keep_count, topk_mask_jax,
                            topk_mask_np)
from repro.core import tradeoff
from repro.core.dda import DDASimulator, stepsize_sqrt
from repro.core.graphs import kregular_expander
from repro.core.schedules import EveryIteration
from repro.kernels import ops as kops
from repro.kernels.ref import compress_mix_ref, gossip_gather_mix_ref


def _quadratic(n, d, seed=0):
    rng = np.random.default_rng(seed)
    targets = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)

    def subgrad_stack(x, t, key):
        return x - targets

    def objective(xbar):
        return jnp.mean(jnp.sum((xbar[None, :] - targets) ** 2, axis=-1))

    return subgrad_stack, jax.jit(objective)


# ---------------------------------------------------------------------------
# registry / spec front door
# ---------------------------------------------------------------------------


def test_registry_inventory():
    assert sorted(COMPRESSORS) == ["int8", "none", "randk", "topk"]
    assert sorted(compressors.names()) == sorted(COMPRESSORS)
    for kind in COMPRESSORS:
        comp = build_compressor(kind)
        assert comp.kind == kind
        # params_dict rebuilds the exact compressor (the spec contract)
        assert build_compressor(kind, comp.params_dict()) == comp


def test_build_compressor_rejects_typos():
    with pytest.raises(ValueError, match="unknown compression kind"):
        build_compressor("top_k")
    with pytest.raises(ValueError, match="bad params"):
        build_compressor("topk", {"kep": 0.5})
    with pytest.raises(ValueError, match="keep"):
        build_compressor("topk", {"keep": 0.0})
    with pytest.raises(ValueError, match="keep"):
        build_compressor("randk", {"keep": 1.5})


def test_wire_ratios_closed_form():
    d = 64
    assert NoCompression().wire_ratio(d) == 1.0
    k = keep_count(d, 0.25)
    assert TopK(keep=0.25).wire_ratio(d) == pytest.approx(
        k * (VALUE_BYTES + INDEX_BYTES) / (d * VALUE_BYTES))
    # rand-k's support is shared randomness: no index bytes on the wire
    assert RandK(keep=0.25).wire_ratio(d) == pytest.approx(k / d)
    assert RandK(keep=0.25).wire_ratio(d) < TopK(keep=0.25).wire_ratio(d)
    assert Int8().wire_ratio(d) == pytest.approx(
        (d + VALUE_BYTES) / (d * VALUE_BYTES))
    # a 1-entry message can never beat the uncompressed float
    assert keep_count(3, 0.01) == 1


# ---------------------------------------------------------------------------
# satellite: the exact-k tie regression (the old dense inline mask kept
# every |x| >= threshold entry, i.e. MORE than k on magnitude ties)
# ---------------------------------------------------------------------------


def test_topk_exact_k_on_magnitude_ties():
    row = np.array([1.0, -1.0, 1.0, -1.0, 0.5, 1.0], np.float32)
    for k in (1, 2, 3):
        m_np = topk_mask_np(row, k)
        m_jx = np.asarray(topk_mask_jax(jnp.asarray(row)[None, :], k))[0]
        assert int(m_np.sum()) == k, "np mask must keep exactly k on ties"
        assert int(m_jx.sum()) == k, "jax mask must keep exactly k on ties"
        # both halves break ties toward the lower index -- identically
        np.testing.assert_array_equal(m_np, m_jx)


def test_topk_jax_np_halves_agree():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(7, 33)).astype(np.float32)
    comp = TopK(keep=0.3)
    sent_jax = np.asarray(
        comp.compress_jax(jnp.asarray(x), jnp.asarray(0, jnp.int32)))
    for i in range(x.shape[0]):
        np.testing.assert_allclose(comp.compress_np(x[i], i, 0),
                                   sent_jax[i], rtol=0, atol=0)


# ---------------------------------------------------------------------------
# property tests: compressor invariants
# ---------------------------------------------------------------------------

_rows = st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                 min_size=2, max_size=48) if HAVE_HYPOTHESIS else None


@settings(max_examples=60, deadline=None)
@given(row=_rows, keep=st.floats(0.01, 1.0), kind=st.sampled_from(
    ["topk", "randk"]), node=st.integers(0, 7), stamp=st.integers(0, 99))
def test_sparsifier_support_invariants(row, keep, kind, node, stamp):
    """decompress(compress(x)) == x on the support, 0 off it, support
    size exactly keep_count(d, keep), and the sent values are VERBATIM
    coordinates of x (sparsification never rescales)."""
    x = np.asarray(row, np.float32)
    d = x.shape[0]
    comp = build_compressor(kind, {"keep": keep})
    sent = comp.compress_np(x, node, stamp)
    on = sent != 0.0
    assert int(on.sum()) <= keep_count(d, keep)
    np.testing.assert_array_equal(sent[on], x[on])
    # exactly k nonzero when x is nonzero everywhere on the support
    strict = np.abs(x) > 0
    if strict.all():
        assert int(on.sum()) == keep_count(d, keep)
    # determinism: the same (seed, node, stamp) replays the same support
    np.testing.assert_array_equal(sent, comp.compress_np(x, node, stamp))


@settings(max_examples=60, deadline=None)
@given(row=_rows, stochastic=st.booleans(), node=st.integers(0, 7),
       stamp=st.integers(0, 99))
def test_quantizer_range_bounds(row, stochastic, node, stamp):
    """Int8 absmax quantization: per-entry error <= one quantization step
    s = max|x|/127, output bounded by max|x|, zero maps to zero."""
    x = np.asarray(row, np.float32)
    comp = Int8(stochastic=stochastic, seed=3)
    sent = comp.compress_np(x, node, stamp)
    s = float(np.max(np.abs(x))) / Int8.LEVELS
    if s == 0.0:
        np.testing.assert_array_equal(sent, x)
        return
    assert np.max(np.abs(sent - x)) <= s * (1.0 + 1e-6)
    assert np.max(np.abs(sent)) <= np.max(np.abs(x)) * (1.0 + 1e-6)
    if not stochastic:
        np.testing.assert_array_equal(sent, comp.compress_np(x, node, stamp))


@settings(max_examples=30, deadline=None)
@given(kind=st.sampled_from(["topk", "randk", "int8"]),
       seed=st.integers(0, 99), rounds=st.integers(1, 12))
def test_error_feedback_telescopes(kind, seed, rounds):
    """sum(sent) == sum(msg) + res_0 - res_T: with error feedback the
    cumulative transmitted mass is exactly the cumulative message mass
    up to the final residual -- the unbiasedness EF buys."""
    rng = np.random.default_rng(seed)
    d = 24
    params = {"keep": 0.25} if kind in ("topk", "randk") else {}
    comp = build_compressor(kind, params)
    assert comp.error_feedback
    res = np.zeros(d, np.float32)
    total_sent = np.zeros(d, np.float64)
    total_msg = np.zeros(d, np.float64)
    for t in range(rounds):
        msg = rng.normal(size=d).astype(np.float32)
        corrected = msg + res
        sent = comp.compress_np(corrected, 0, t)
        res = corrected - sent
        total_sent += sent
        total_msg += msg
    np.testing.assert_allclose(total_sent, total_msg - res, atol=1e-3)


# ---------------------------------------------------------------------------
# the fused compress-mix pass
# ---------------------------------------------------------------------------


def _sparse_inputs(n=8, k=4, d=40, seed=0):
    rng = np.random.default_rng(seed)
    g = kregular_expander(n, k=k, seed=1)
    S_in = np.stack([np.asarray(p, np.int64) for p in g.perms], axis=1)
    z = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    msg = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=(n, d)), jnp.float32)
    return g, jnp.asarray(S_in), z, msg, mask


def test_compress_mix_kernel_matches_ref():
    """The Pallas kernel (interpret mode on CPU) against the pure-jnp ref,
    scalar and per-edge weights."""
    g, S_in, z, msg, mask = _sparse_inputs()
    n, k = S_in.shape
    for w_self, w_edge in [
        (jnp.float32(g.self_weight), jnp.float32(g.edge_weight)),
        (jnp.asarray(np.random.default_rng(2).uniform(0.1, 0.5, n),
                     jnp.float32),
         jnp.asarray(np.random.default_rng(3).uniform(0.01, 0.2, (n, k)),
                     jnp.float32)),
    ]:
        want = compress_mix_ref(z, msg, mask, S_in, w_self, w_edge)
        got = kops.compress_mix_impl(z, msg, mask, S_in, w_self, w_edge,
                                     interpret=True, use_kernel=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_compress_mix_matches_dense_matmul_oracle():
    """The fused sparse pass == the dense-matmul oracle
    diag(P) z + P_off (msg ⊙ mask): the acceptance gate for the sparse
    path staying available under compression."""
    g, S_in, z, msg, mask = _sparse_inputs()
    got = kops.compress_mix_impl(z, msg, mask, S_in,
                                 jnp.float32(g.self_weight),
                                 jnp.float32(g.edge_weight))
    P = np.asarray(g.mixing_matrix(), np.float64)
    sent = np.asarray(msg, np.float64) * np.asarray(mask, np.float64)
    want = (np.diag(P)[:, None] * np.asarray(z, np.float64)
            + (P - np.diag(np.diag(P))) @ sent)
    rel = (np.linalg.norm(np.asarray(got, np.float64) - want)
           / np.linalg.norm(want))
    assert rel <= 1e-5, f"fused pass vs dense oracle rel={rel:.2e}"


def test_gather_mix_msg_matches_ref():
    """The msg= variant (quantizer path: dense dequantized messages ride
    the plain gather) against its ref."""
    g, S_in, z, msg, _ = _sparse_inputs()
    want = gossip_gather_mix_ref(z, S_in, jnp.float32(g.self_weight),
                                 jnp.float32(g.edge_weight), msg=msg)
    got = kops.gossip_gather_mix_impl(z, S_in, jnp.float32(g.self_weight),
                                      jnp.float32(g.edge_weight), msg=msg,
                                      interpret=True, use_kernel=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# DDASimulator integration
# ---------------------------------------------------------------------------


def test_topk_on_kregular_graph_stays_sparse():
    """Acceptance: compression no longer disqualifies the sparse path --
    the fused compress-mix kernel is why."""
    n, d = 12, 10
    subgrad, obj = _quadratic(n, d)
    g = kregular_expander(n, k=4, seed=0)
    sim = DDASimulator(subgrad, obj, g, EveryIteration(),
                       a_fn=stepsize_sqrt(0.1),
                       compression=TopK(keep=0.25))
    assert sim.mix_mode == "sparse"
    assert sim.wire_ratio(d) == TopK(keep=0.25).wire_ratio(d)


def test_sparse_vs_dense_mix_identical_under_compression(dense_mix):
    """The fused sparse path and the forced dense-matmul path run the SAME
    compressed algorithm: traces agree to float tolerance."""
    n, d, T = 12, 10, 80
    subgrad, obj = _quadratic(n, d)
    g = kregular_expander(n, k=4, seed=0)
    make = lambda: DDASimulator(subgrad, obj, g, EveryIteration(),
                                a_fn=stepsize_sqrt(0.1),
                                compression=TopK(keep=0.25))
    sims = {"sparse": make()}
    with dense_mix():
        sims["dense"] = make()
    traces = {}
    for mix, sim in sims.items():
        assert sim.mix_mode == mix
        traces[mix] = sim.run(jnp.zeros((n, d)), T, eval_every=20)
    a = np.asarray(traces["sparse"].fvals)
    b = np.asarray(traces["dense"].fvals)
    assert np.max(np.abs(a - b)) / np.max(np.abs(b)) < 1e-5


def test_none_compression_is_bit_identical_to_seed_path():
    """kind='none' normalizes away: the program, trace and time axis are
    byte-for-byte those of an uncompressed run."""
    n, d, T = 8, 6, 60
    subgrad, obj = _quadratic(n, d)
    g = kregular_expander(n, k=4, seed=0)
    mk = lambda comp: DDASimulator(subgrad, obj, g, EveryIteration(),
                                   a_fn=stepsize_sqrt(0.1), r=0.05,
                                   compression=comp)
    sim_none = mk(NoCompression())
    assert sim_none.compression is None
    t0 = mk(None).run(jnp.zeros((n, d)), T, eval_every=20)
    t1 = sim_none.run(jnp.zeros((n, d)), T, eval_every=20)
    assert t0.fvals == t1.fvals
    assert t0.sim_time == t1.sim_time


def test_compressed_time_axis_charges_r_times_c():
    """The dense sim_time charges the effective tradeoff r*c."""
    n, d, T = 8, 16, 40
    subgrad, obj = _quadratic(n, d)
    g = kregular_expander(n, k=4, seed=0)
    r = 0.2
    plain = DDASimulator(subgrad, obj, g, EveryIteration(),
                         a_fn=stepsize_sqrt(0.1), r=r)
    comp = DDASimulator(subgrad, obj, g, EveryIteration(),
                        a_fn=stepsize_sqrt(0.1), r=r,
                        compression=RandK(keep=0.25))
    c = RandK(keep=0.25).wire_ratio(d)
    tp = plain.run(jnp.zeros((n, d)), T, eval_every=20)
    tc = comp.run(jnp.zeros((n, d)), T, eval_every=20)
    k = g.degree
    for it, s_plain, s_comp in zip(tp.iters, tp.sim_time, tc.sim_time):
        # every iteration communicates here: s = it/n + it*k*r(*c)
        assert s_plain == pytest.approx(it * (1.0 / n + k * r))
        assert s_comp == pytest.approx(it * (1.0 / n + k * r * c))


def test_error_feedback_compressed_run_converges():
    """Top-k at keep=0.25 with EF tracks the uncompressed objective."""
    n, d, T = 12, 10, 300
    subgrad, obj = _quadratic(n, d)
    g = kregular_expander(n, k=4, seed=0)
    base = DDASimulator(subgrad, obj, g, EveryIteration(),
                        a_fn=stepsize_sqrt(0.1))
    comp = DDASimulator(subgrad, obj, g, EveryIteration(),
                        a_fn=stepsize_sqrt(0.1),
                        compression=TopK(keep=0.25))
    t0 = base.run(jnp.zeros((n, d)), T, eval_every=50)
    t1 = comp.run(jnp.zeros((n, d)), T, eval_every=50)
    assert t1.fvals[-1] < 1.2 * t0.fvals[-1] + 0.5
    # the residual-norm trajectory was recorded
    assert comp.last_res_norms is not None
    assert len(comp.last_res_norms) == len(t1.fvals)


# ---------------------------------------------------------------------------
# netsim: engine bit-identity under compression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comp", [
    TopK(keep=0.3), RandK(keep=0.3, seed=7), Int8(),
    Int8(stochastic=True, seed=3),
], ids=lambda c: f"{c.kind}{'-st' if getattr(c, 'stochastic', 0) else ''}")
def test_netsim_engines_bit_identical_under_compression(comp):
    """Object and vectorized engines produce EXACTLY the same trace,
    residuals and wire accounting under every compressor -- randomized
    compressors key their RNG on (seed, node, stamp), a pure function of
    what is sent, never of global event order."""
    from repro.netsim import NetSimulator
    from repro.netsim.scenarios import homogeneous

    n, d, T = 8, 12, 60
    rng = np.random.default_rng(0)
    targets = rng.normal(size=(n, d))

    def grad_fn(i, x, t):
        return x - targets[i]

    def eval_fn(xbar):
        return float(np.mean(np.sum((xbar[None] - targets) ** 2, -1)))

    runs = {}
    for engine in ("object", "vectorized"):
        sim = NetSimulator(homogeneous(n, r=0.05), grad_fn, eval_fn,
                           schedule=EveryIteration(), seed=1,
                           engine=engine, compression=comp)
        tr = sim.run(np.zeros((n, d)), T, eval_every=15)
        runs[engine] = (tr, sim.comp_res_norms, sim.net.wire_bytes)
    (ta, ra, wa), (tb, rb, wb) = runs["object"], runs["vectorized"]
    assert ta.fvals == tb.fvals
    assert ta.sim_time == tb.sim_time
    assert ta.disagreement == tb.disagreement
    assert ra == rb and len(ra) == len(ta.fvals)
    assert wa == wb == sim.net.message_bytes * comp.wire_ratio(d)


def test_netsim_compression_validation():
    from repro.netsim import NetSimulator
    from repro.netsim.scenarios import homogeneous

    grad = lambda i, x, t: x
    ev = lambda xbar: 0.0
    with pytest.raises(ValueError, match="algorithm='dda'"):
        NetSimulator(homogeneous(4, r=0.05), grad, ev,
                     schedule=EveryIteration(), algorithm="pushsum",
                     compression=TopK(keep=0.5))
    with pytest.raises(TypeError, match="Compressor"):
        NetSimulator(homogeneous(4, r=0.05), grad, ev,
                     schedule=EveryIteration(), compression="topk")


# ---------------------------------------------------------------------------
# tradeoff: the c axis
# ---------------------------------------------------------------------------


def test_tradeoff_c_axis_shifts_optima():
    n, k, r, lam2 = 16, 4, 0.1, 0.6
    c = 0.25
    assert tradeoff.iteration_cost(n, k, r, c) == pytest.approx(
        1.0 / n + k * r * c)
    # compression enlarges the optimal cluster by 1/sqrt(c) ...
    assert tradeoff.n_opt_complete(r, c) == pytest.approx(
        tradeoff.n_opt_complete(r * c))
    # ... and pulls h_opt back toward 1 by sqrt(c)
    assert tradeoff.h_opt(n, k, r, lam2, c) == pytest.approx(
        tradeoff.h_opt(n, k, r * c, lam2))
    # tau is monotone improving in compression on comm-bound regimes
    taus = [tradeoff.time_to_accuracy(0.1, n, k, r, lam2, c=ci)
            for ci in (1.0, 0.5, 0.25)]
    assert taus[0] > taus[1] > taus[2]
    assert tradeoff.time_to_accuracy(0.1, n, k, r, lam2, c=1.0) == \
        tradeoff.time_to_accuracy(0.1, n, k, r, lam2)


def test_hopt_with_rc_predicts_frontier_ordering():
    """Acceptance: h_opt evaluated at r*c orders the measured dense
    time-to-accuracy frontier across compression ratios -- cheaper wires
    favor denser communication."""
    n, k, r, lam2 = 16, 4, 0.5, 0.7
    h_plain = tradeoff.h_opt(n, k, r, lam2)
    h_comp = tradeoff.h_opt(n, k, r, lam2, c=0.1)
    assert h_comp < h_plain  # communicate more often when messages shrink
