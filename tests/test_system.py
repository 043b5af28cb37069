"""End-to-end behaviour tests for the whole system on a single device:
training loop + schedules + checkpointing + data pipeline wired together."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedules import IncreasinglySparse
from repro.data.pipeline import TokenStream
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import registry, transformer
from repro.optim import adamw, constant_lr, dual_averaging, rsqrt_lr, sgd


def test_train_step_reduces_loss_single_device():
    cfg = registry.get_config("llama3-8b", "smoke")
    opt = adamw(constant_lr(2e-3))
    step = jax.jit(make_train_step(cfg, opt))
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    state = opt.init(params)
    stream = TokenStream(cfg.vocab_size, 32, 8, seed=0)
    losses = []
    for _ in range(15):
        params, state, metrics = step(params, state, next(stream))
        losses.append(float(metrics["loss"]))
    stream.close()
    assert losses[-1] < losses[0]


def test_train_step_with_microbatching_matches_full_batch():
    """Gradient accumulation must be numerically equivalent (up to fp
    reassociation) to the full-batch step."""
    cfg = registry.get_config("musicgen-medium", "smoke")
    opt = sgd(constant_lr(1e-2))
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                          cfg.vocab_size)}
    batch["labels"] = batch["tokens"]
    p1, _, m1 = jax.jit(make_train_step(cfg, opt))(params, opt.init(params),
                                                   batch)
    p4, _, m4 = jax.jit(make_train_step(cfg, opt, microbatches=4))(
        params, opt.init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=2e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-3)


def test_dual_averaging_optimizer_trains():
    """Faithful DDA inner update as the LM optimizer (paper's algorithm on
    the substrate model)."""
    cfg = registry.get_config("musicgen-medium", "smoke")
    opt = dual_averaging(rsqrt_lr(0.5))
    step = jax.jit(make_train_step(cfg, opt))
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    state = opt.init(params)
    stream = TokenStream(cfg.vocab_size, 32, 8, seed=1)
    losses = []
    for _ in range(20):
        params, state, metrics = step(params, state, next(stream))
        losses.append(float(metrics["loss"]))
    stream.close()
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_serve_step_greedy_decode_runs():
    cfg = registry.get_config("llama3-8b", "smoke")
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    cache = transformer.init_cache(cfg, 2, 8)
    tok = jnp.zeros((2, 1), jnp.int32)
    for pos in range(8):
        logits, cache = serve(params, cache, tok, jnp.int32(pos))
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    assert logits.shape == (2, 1, cfg.vocab_size)


def test_token_stream_determinism():
    a = TokenStream(512, 16, 4, node_index=0, seed=7)
    b = TokenStream(512, 16, 4, node_index=0, seed=7)
    c = TokenStream(512, 16, 4, node_index=1, seed=7)
    ba, bb, bc = next(a), next(b), next(c)
    np.testing.assert_array_equal(np.asarray(ba["tokens"]),
                                  np.asarray(bb["tokens"]))
    assert not np.array_equal(np.asarray(ba["tokens"]),
                              np.asarray(bc["tokens"]))
    for s in (a, b, c):
        s.close()


def test_sparse_schedule_in_training_loop():
    """The t^p schedule drives the launcher correctly: comm rounds ==
    H_T from the schedule."""
    sched = IncreasinglySparse(p=0.3)
    T = 40
    comm_steps = [t for t in range(1, T + 1) if sched.is_comm_step(t)]
    assert len(comm_steps) == sched.H(T)
    assert comm_steps[0] == 1  # first round communicates


def test_adamw_bf16_moments_trains():
    """opt_moments_bf16 path (400B-class memory knob): still trains."""
    import jax.numpy as jnp
    cfg = registry.get_config("musicgen-medium", "smoke")
    opt = adamw(constant_lr(2e-3), moment_dtype=jnp.bfloat16)
    step = jax.jit(make_train_step(cfg, opt))
    params, _ = transformer.init(jax.random.PRNGKey(0), cfg)
    state = opt.init(params)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(state.inner["m"]))
    stream = TokenStream(cfg.vocab_size, 32, 8, seed=2)
    losses = []
    for _ in range(12):
        params, state, metrics = step(params, state, next(stream))
        losses.append(float(metrics["loss"]))
    stream.close()
    assert losses[-1] < losses[0]


def test_compile_cache_dir_is_fixed_per_checkout(tmp_path, monkeypatch):
    """The entry points' compile cache: `JAX_COMPILATION_CACHE_DIR` is left
    to JAX (nothing set in code); otherwise `.jax_cache` at the checkout
    root, the same path whatever the working directory."""
    import pathlib

    from repro.runtime.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert enable_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = pathlib.Path(__file__).resolve().parents[1]
    try:
        paths = set()
        for cwd in (tmp_path, root / "src"):
            monkeypatch.chdir(cwd)
            paths.add(enable_compile_cache())
        assert paths == {str(root / ".jax_cache")}
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
