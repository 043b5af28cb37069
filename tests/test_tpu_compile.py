"""Compile the gossip kernels and the dense scan program for a described
TPU v5e chip -- no chip attached -- and check that the Pallas kernel is in
the compiled program (`tpu_custom_call` in its HLO).

The TPU compiler refuses what interpret mode accepts (unaligned slices,
too much VMEM, a program too large for the device), so these compiles
guard the chip path at real widths from the CPU. Each kernel's call
carries its name, which is how a device trace names it. The topology is
described inside a module-scoped fixture, never at import: only one
process may load the TPU library, and under xdist every worker imports
this file. The last test checks, on the CPU, that the ops pick the jnp
reference here and the kernel when the platform is a TPU.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.experiments import ExperimentSpec
from repro.kernels import ops, ref
from repro.kernels.compress_mix import compress_mix_weighted
from repro.kernels.gossip_mix import gossip_mix_weighted

N, M, K = 256, 4096, 4


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    # (the reset drops the process's memo of whether the cache is in use)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name="gossip_mix_weighted"):
    """The compiled program calls the Pallas kernel, under its own name."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", text), name


def test_gossip_mix_weighted_compiles_for_v5e(one_chip):
    f = jax.jit(lambda *a: gossip_mix_weighted(*a))
    _assert_kernel(f.lower(_sds((N, M), jnp.float32, one_chip),
                           _sds((K, N, M), jnp.float32, one_chip),
                           _sds((N,), jnp.float32, one_chip),
                           _sds((N, K), jnp.float32, one_chip)).compile())


def test_compress_mix_weighted_compiles_for_v5e(one_chip):
    f = jax.jit(lambda *a: compress_mix_weighted(*a))
    _assert_kernel(f.lower(_sds((N, M), jnp.float32, one_chip),
                           _sds((K, N, M), jnp.float32, one_chip),
                           _sds((K, N, M), jnp.float32, one_chip),
                           _sds((N,), jnp.float32, one_chip),
                           _sds((N, K), jnp.float32, one_chip)).compile(),
                   "compress_mix_weighted")


def test_dense_scan_program_compiles_for_v5e(one_chip, monkeypatch):
    """The whole-run scan of `quadratic_consensus` at n=256, d=4096, both
    program variants (cond-free all-comm and per-iteration `lax.cond`),
    traced with the platform check steered to the TPU."""
    from repro.experiments.runner import _dense_parts, _dense_sim
    spec = ExperimentSpec(
        name="tpu_compile",
        problem={"kind": "quadratic_consensus",
                 "params": {"n": N, "d": M, "seed": 0}},
        topology={"kind": "expander", "params": {"k": K, "seed": 0}},
        schedule={"kind": "every"}, backends=[{"kind": "dense"}],
        T=100, eval_every=25, seed=0)
    sim = _dense_sim(spec, _dense_parts(spec, spec.backends[0]))
    assert sim.mix_mode == "sparse"
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    S, E = 4, 25
    state = tuple(_sds((N, M), jnp.float32, one_chip) for _ in range(4)) + (
        _sds((), jnp.float32, one_chip),)
    args = (state, _sds((S, E), jnp.bool_, one_chip),
            _sds((S,), jnp.int32, one_chip),
            _sds((2,), jnp.uint32, one_chip))
    for always_comm in (True, False):
        _assert_kernel(sim._scan_jits[always_comm].lower(*args).compile())


def test_ops_pick_reference_on_cpu_and_kernel_on_tpu(monkeypatch):
    """Dispatch follows the platform: on the CPU the gossip ops run the jnp
    reference (no pallas_call in the program); when the platform check
    says TPU they call the kernel. The CPU values agree with the oracle."""
    rng = np.random.default_rng(0)
    n, k, d = 16, 4, 256
    z = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    S_in = jnp.asarray(rng.integers(0, n, size=(n, k)), jnp.int32)
    mask = jnp.asarray(rng.random((n, d)) < 0.25, jnp.float32)
    w_self, w_edge = jnp.float32(0.2), jnp.float32(0.2)

    def gossip(z):
        return ops.gossip_gather_mix_impl(z, S_in, w_self, w_edge)

    def compress(z):
        return ops.compress_mix_impl(z, z, mask, S_in, w_self, w_edge)

    def has_pallas(fn):
        # a fresh callable each time: traces are cached per function
        return "pallas_call" in str(jax.make_jaxpr(lambda x: fn(x))(z))

    assert jax.default_backend() == "cpu"
    assert not has_pallas(gossip) and not has_pallas(compress)
    np.testing.assert_array_equal(
        np.asarray(gossip(z)),
        np.asarray(ref.gossip_gather_mix_ref(z, S_in, w_self, w_edge)))
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert has_pallas(gossip) and has_pallas(compress)
