"""Compile the gossip kernels, the non-smooth subgradient kernel and the
dense scan programs for a described TPU v5e chip -- no chip attached --
and check that the Pallas kernel is in the compiled program
(`tpu_custom_call` in its HLO).

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
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        described = topologies.get_topology_desc(platform="tpu",
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
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


def test_nonsmooth_subgrad_compiles_for_v5e(one_chip):
    """The section V.B subgradient kernel at the benchmark's size: n=256
    nodes, M=30 pairs of centres of width d=4096; and vmapped over two
    lanes that share the centres, as `run_batch` calls it."""
    from repro.kernels.nonsmooth_subgrad import nonsmooth_subgrad
    centers = _sds((30, 2, N, M), jnp.float32, one_chip)
    f = jax.jit(lambda x, c: nonsmooth_subgrad(x, c))
    _assert_kernel(f.lower(_sds((N, M), jnp.float32, one_chip), centers)
                   .compile(), "nonsmooth_subgrad")
    lanes = jax.jit(jax.vmap(nonsmooth_subgrad, in_axes=(0, None)))
    _assert_kernel(lanes.lower(_sds((2, N, M), jnp.float32, one_chip),
                               centers).compile(), "vmap_nonsmooth_subgrad_")


def test_nonsmooth_objective_compiles_to_a_highest_dot_for_v5e(one_chip):
    """The section V.B objective vmapped over the n=256 node averages at
    the benchmark's size (M=30, d=4096), as the simulator's evaluation
    runs it: its cross terms against the (2nM, d) rows of centres compile
    to one dot (XLA may write it as a convolution) at "highest" operand
    precision, and no fusion of the direct form's f32[256,256,30,2]
    squared distances, built from the elementwise differences, is left.
    The direct form shows both checks can see it."""
    n, pairs = N, 30
    x = _sds((n, M), jnp.float32, one_chip)
    centers = _sds((n, pairs, 2, M), jnp.float32, one_chip)
    split = jax.jit(jax.vmap(ops.nonsmooth_objective, in_axes=(0, None, None)))
    direct = jax.jit(jax.vmap(ref.nonsmooth_objective_ref, in_axes=(0, None)))
    dot = re.compile(r"= f32\[[\d,]*\]\S* (convolution|dot)\(.*")
    squared = re.compile(rf"fusion\S* = f32\[{n},{n},{pairs},2\]\S* fusion\(")

    text = split.lower(x, _sds((n * pairs * 2, M), jnp.float32, one_chip),
                       _sds((n, pairs, 2), jnp.float32, one_chip)
                       ).compile().as_text()
    dots = [m.group(0) for m in dot.finditer(text)]
    assert len(dots) == 1, dots
    assert "operand_precision={highest,highest}" in dots[0]
    assert not squared.search(text)
    assert f"f32[{n},{n},{pairs},2,{M}]" not in text

    text = direct.lower(x, centers).compile().as_text()
    assert not dot.search(text) and squared.search(text)
    assert f"f32[{n},{n},{pairs},2,{M}]" in text


def test_nonsmooth_scan_program_compiles_for_v5e(one_chip, monkeypatch):
    """The whole-run scan of the `nonsmooth` problem (n=8, M=30, d=4096),
    built and traced with the platform check steered to the TPU: its
    subgradient is the Pallas kernel, a `tpu_custom_call` in the HLO."""
    from repro.experiments import runner
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(runner, "_PROBLEM_CACHE", {})
    n = 8
    spec = ExperimentSpec(
        name="tpu_compile",
        problem={"kind": "nonsmooth",
                 "params": {"n": n, "M": 30, "d": M, "seed": 0}},
        topology={"kind": "expander", "params": {"k": K, "seed": 0}},
        schedule={"kind": "every"}, backends=[{"kind": "dense"}],
        T=100, eval_every=25, seed=0)
    sim = runner._dense_sim(spec, runner._dense_parts(spec,
                                                      spec.backends[0]))
    state = tuple(_sds((n, M), jnp.float32, one_chip) for _ in range(4)) + (
        _sds((), jnp.float32, one_chip),)
    args = (state, _sds((4, 25), jnp.bool_, one_chip),
            _sds((4,), jnp.int32, one_chip),
            _sds((2,), jnp.uint32, one_chip))
    _assert_kernel(sim._scan_jits[True].lower(*args).compile(),
                   "nonsmooth_subgrad")


#: a v5e chip's HBM, 16 GiB
V5E_HBM = 16 * 2 ** 30


@pytest.mark.parametrize("step", ["local", "fused"])
def test_deepseek_v2_lite_share_steps_compile_for_v5e_2x2(topo, step):
    """The `launch.consensus4` cell's step programs at the real size:
    DeepSeek-V2-Lite's EP-8 share (1 dense + 5 MoE layers, 8 of 64
    experts, 1/8 of the vocabulary), one replica per chip of a described
    v5e:2x2, 4 x 4096 tokens a replica. The TPU compiler refuses a
    program that overflows a chip's HBM; its own peak (the donated state
    and the temporaries live at once, 12.6-12.7 GiB) stays under the
    chip's 16 GiB. The sum of arguments and temporaries overstates it by
    3 GB, as they do not all live at once. At 8 sequences a replica the
    compiler refuses the step (16.06 of 15.75 GiB). The held experts'
    grouped matmuls and, in the fused step, the gossip's all-gathers are
    in the program."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.graphs import build_graph
    from repro.launch.train import ConsensusProgram
    from repro.models import registry
    from repro.optim import adamw, cosine_lr
    cfg = registry.get_config("deepseek-v2-lite", "ep8")
    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
                ("pod", "data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 3)
    program = ConsensusProgram(cfg, adamw(cosine_lr(4.2e-4, 8)), mesh,
                               build_graph("complete", 4),
                               batch_per_node=4, seq_len=4096)
    shapes = jax.eval_shape(program._jits["init"], jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, program.state_shardings)
    rows = NamedSharding(mesh, P("pod"))
    batch = {k: _sds((4, 4, 4096), jnp.int32, rows)
             for k in ("tokens", "labels")}
    with program._rules():
        compiled = program._jits[step].lower(*state, batch).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < m.peak_memory_in_bytes < V5E_HBM, \
        m.peak_memory_in_bytes
    text = compiled.as_text()
    assert "ragged-dot-metadata" in text
    assert ("all-gather" in text) == (step == "fused")


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
