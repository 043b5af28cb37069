"""Fault tolerance: deadline gossip, straggler robustness and elastic
rescale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, st

from repro.core.graphs import random_regular_expander
from repro.runtime.elastic import plan_rescale, rescale_state
from repro.runtime.fault_tolerance import StragglerModel, degraded_matrix


@given(seed=st.integers(0, 20))
def test_degraded_matrix_row_stochastic(seed):
    g = random_regular_expander(10, k=4, seed=0)
    rng = np.random.default_rng(seed)
    arrived = rng.random(10) > 0.3
    P = degraded_matrix(g, arrived)
    assert np.allclose(P.sum(axis=1), 1.0, atol=1e-9)
    assert (P >= -1e-12).all()
    # columns of missing nodes are zeroed except self
    for j in range(10):
        if not arrived[j]:
            col = P[:, j].copy()
            col[j] = 0.0
            assert np.allclose(col, 0.0)


def test_consensus_with_random_drops_still_converges():
    """Gossip with 30% dropped messages per round still mixes to (near)
    consensus -- the paper's robustness claim, empirically."""
    g = random_regular_expander(12, k=4, seed=1)
    rng = np.random.default_rng(0)
    z = rng.normal(size=(12, 5)).astype(np.float64)
    for _ in range(300):
        arrived = rng.random(12) > 0.3
        P = degraded_matrix(g, arrived)
        z = P @ z
    assert np.max(np.std(z, axis=0)) < 1e-3


def test_straggler_model_deadline():
    m = StragglerModel(p_slow=0.5, m_slow=8.0, deadline=2.0, seed=0)
    times = m.sample_round(1000)
    assert set(np.unique(times)) <= {1.0, 8.0}
    mask = m.arrival_mask(1000)
    # slow nodes (8.0 > 2.0) miss the deadline
    assert 0.3 < mask.mean() < 0.7


def test_elastic_rescale_shrink_and_grow():
    state = {"w": jnp.arange(8.0).reshape(4, 2)}
    # 4 -> 3 nodes, node 1 failed
    plan = plan_rescale("complete", 4, 3, m_rows=120, failed=[1])
    out = rescale_state(state, plan)
    assert out["w"].shape == (3, 2)
    np.testing.assert_allclose(np.asarray(out["w"][0]), [0, 1])  # node 0
    np.testing.assert_allclose(np.asarray(out["w"][1]), [4, 5])  # node 2
    # grow 3 -> 5: new rows = survivors' mean
    plan2 = plan_rescale("complete", 3, 5, m_rows=120)
    out2 = rescale_state({"w": out["w"]}, plan2)
    assert out2["w"].shape == (5, 2)
    np.testing.assert_allclose(np.asarray(out2["w"][3]),
                               np.asarray(out["w"]).mean(0), rtol=1e-6)
    # data slices cover the whole dataset
    assert sum(s.stop - s.start for s in plan2.data_slices) == 120


def test_straggler_arrival_mask_seeded_determinism():
    """Two models with the same seed draw identical mask sequences (the
    netsim/benchmarks reproducibility contract); a different seed diverges."""
    a = StragglerModel(p_slow=0.3, m_slow=4.0, deadline=2.0, seed=11)
    b = StragglerModel(p_slow=0.3, m_slow=4.0, deadline=2.0, seed=11)
    c = StragglerModel(p_slow=0.3, m_slow=4.0, deadline=2.0, seed=12)
    seq_a = [a.arrival_mask(64) for _ in range(5)]
    seq_b = [b.arrival_mask(64) for _ in range(5)]
    seq_c = [c.arrival_mask(64) for _ in range(5)]
    for ma, mb in zip(seq_a, seq_b):
        np.testing.assert_array_equal(ma, mb)
    assert any(not np.array_equal(ma, mc)
               for ma, mc in zip(seq_a, seq_c))
    # consecutive draws from ONE model advance its stream (not frozen)
    assert any(not np.array_equal(seq_a[0], m) for m in seq_a[1:])


def test_plan_rescale_rejects_out_of_range_failed_ids():
    with pytest.raises(ValueError, match=r"failed ids \[4\] out of range"):
        plan_rescale("complete", 4, 3, m_rows=100, failed=[4])
    with pytest.raises(ValueError, match="out of range"):
        plan_rescale("complete", 4, 3, m_rows=100, failed=[-1])


def test_plan_rescale_rejects_all_failed():
    with pytest.raises(ValueError, match="no survivors"):
        plan_rescale("complete", 3, 3, m_rows=100, failed=[0, 1, 2])


def test_elastic_rescale_grow_from_single_survivor():
    """Degenerate shrink-to-one then grow: every new row must equal the
    lone survivor (its mean is itself)."""
    state = {"w": jnp.arange(6.0).reshape(3, 2)}
    plan = plan_rescale("complete", 3, 4, m_rows=40, failed=[0, 2])
    out = rescale_state(state, plan)
    assert out["w"].shape == (4, 2)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out["w"][i]), [2, 3])
