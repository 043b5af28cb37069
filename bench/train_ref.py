"""Plain consensus training, the reference the benchmark checks the
`launch` backend's training runs against.

n replicas of one model train on disjoint token streams and average their
parameters over the configuration's graph when the schedule says so. It
is written out from what the launch backend states it does, and imports
nothing of the program under test. A problem module under
`bench/problems/` supplies the plain model, built from the configuration's
own sizes:

    init(key, cfg)                         -> params, each leaf in the
                                              dtype the configuration
                                              stores it in
    loss(params, tokens, labels, cfg, dtype) -> mean token cross-entropy,
                                              computed in `dtype`

and takes this module's hooks as its own, so that the harness drives and
judges its cells as training:

    from bench.train_ref import (NUMBERS, readings, reference_trace,
                                 spec_problem)

With replica i's parameters p_i, at each step t = 1 .. T:

    tokens_i(t)   the launch backend's token stream, written out again
                  (`batch_tokens`), at stream step t - 1
    l_i, g_i      the loss at p_i and its gradient, in float32 with matrix
                  products at "highest" (the reference's precision)
    p_i           AdamW with the program's constants (below) and a cosine
                  learning rate from the backend's `lr` over T, computed in
                  float32 and stored back in each leaf's dtype
    p             P p in float32 at the steps where the schedule
                  communicates (every step; periodic: t > 1 and
                  (t - 1) % h == 0), after the optimizer step

At every `eval_every`-th step the trace records the mean over replicas of
l_i(t), each replica's loss before its update, as the program's trace
does. The replicas are the rows of stacked arrays, spread over as many
devices as divide their number, so that each chip holds its share.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, dda_ref
from bench.generator import SEED_MODULUS

#: the numbers a training cell compares (`bench/check.py` judges them)
NUMBERS = ("loss_rel_gap", "trace_layout_mismatch")

#: AdamW's constants as the launch backend builds its optimizer
B1, B2, EPS, WEIGHT_DECAY = 0.9, 0.95, 1e-8, 0.1

#: schedules whose communication steps this reference models
SCHEDULES = ("every", "periodic")


def spec_problem(cfg: dict, seed: int) -> dict:
    """The spec's problem: the model and its batch, with no seed; the
    launch backend draws weights and tokens from the spec's own seed."""
    return dict(cfg["problem"])


def communicates(schedule: dict, t: int) -> bool:
    """Whether step t (from 1) ends with a gossip round."""
    kind = schedule["kind"]
    if kind == "every":
        return True
    if kind == "periodic":
        h = schedule.get("params", {}).get("h", 1)
        return t > 1 and (t - 1) % h == 0
    raise ValueError(f"the training reference has no {kind!r} schedule; "
                     f"it models {SCHEDULES}")


def batch_tokens(seed: int, replica: int, step: int, vocab: int, batch: int,
                 seq_len: int) -> np.ndarray:
    """Replica `replica`'s (batch, seq_len + 1) tokens at stream step
    `step`: Zipf(1.3) draws folded into the vocabulary, every odd
    position set by its left neighbour (a bigram the model can learn)."""
    rng = np.random.default_rng((seed * 1_000_003 + replica) * 977 + step)
    toks = (rng.zipf(1.3, size=(batch, seq_len + 1)).astype(np.int64)
            - 1) % vocab
    toks[:, 1::2] = (toks[:, 0::2][:, :toks[:, 1::2].shape[1]] * 31
                     + 7) % vocab
    return toks.astype(np.int32)


def replicas(cfg: dict) -> int:
    """One replica per entry of the mesh's `pod` axis."""
    return int(cfg["backend"]["params"]["mesh"][0])


def mixing(cfg: dict) -> np.ndarray:
    n = replicas(cfg)
    m = cfg["mixing"]
    if m.get("complete"):
        return dda_ref.complete_matrix(n)
    return dda_ref.mixing_matrix(n, m["shifts"], m["self_weight"],
                                 m["edge_weight"])


def cosine_lr(peak: float, T: int, t):
    """The learning rate at step t (from 1), in float32."""
    frac = jnp.clip(t.astype(jnp.float32) / T, 0.0, 1.0)
    return 0.5 * peak * (1.0 + jnp.cos(jnp.pi * frac))


def adamw(params, grads, m, v, t, lr):
    """One AdamW step of one replica: (params, m, v) after step t."""
    tf = t.astype(jnp.float32)
    c1 = 1.0 - B1 ** tf
    c2 = 1.0 - B2 ** tf

    def one(p, g, m, v):
        p32 = p.astype(jnp.float32)
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        upd = (m / c1) / (jnp.sqrt(v / c2) + EPS)
        return (p32 - lr * (upd + WEIGHT_DECAY * p32)).astype(p.dtype), m, v

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def reference_trace(module, cfg: dict, traffic: dict, seed: int,
                    dtype: str, matmul_precision: str | None
                    ) -> dict[str, np.ndarray]:
    """The trace of the training run that `seed` makes, with the loss and
    its gradient computed in `dtype` and matrix products at
    `matmul_precision`: {"loss": mean over replicas of the loss at each
    `eval_every`-th step}, float64. The hook `bench/check.py` calls for a
    training cell; `module` is the cell's problem module."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    backend = cfg["backend"]["params"]
    if backend.get("mix_target", "params") != "params":
        raise ValueError("the training reference gossips parameters only")
    n = replicas(cfg)
    T, every = traffic["T"], traffic["eval_every"]
    prob = cfg["problem"]["params"]
    batch, seq_len = prob["batch_per_node"], prob["seq_len"]
    spec_seed = int(seed) % SEED_MODULUS
    lr_peak = float(backend["lr"])

    devices = jax.devices()
    share = max(d for d in range(1, len(devices) + 1) if n % d == 0)
    mesh = Mesh(np.array(devices[:share]), ("replica",))
    rows = NamedSharding(mesh, PartitionSpec("replica"))
    P = jnp.asarray(mixing(cfg), jnp.float32)
    dtype = jnp.dtype(dtype)

    def step_one(params, m, v, t, toks):
        value, grads = jax.value_and_grad(
            lambda p: module.loss(p, toks[:, :-1], toks[:, 1:], cfg, dtype))(
            jax.tree.map(lambda a: a.astype(jnp.float32), params))
        params, m, v = adamw(params, grads, m, v, t,
                             cosine_lr(lr_peak, max(T, 1), t))
        return value, params, m, v

    def gossip(params):
        return jax.tree.map(
            lambda a: jnp.einsum("pq,q...->p...", P, a.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST
                                 ).astype(a.dtype), params)

    with jax.default_matmul_precision(matmul_precision):
        init = jax.jit(jax.vmap(lambda k: module.init(k, cfg)),
                       out_shardings=rows)
        params = init(jax.random.split(jax.random.PRNGKey(spec_seed), n))
        zeros = jax.jit(lambda p: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), p),
            out_shardings=rows)
        m, v = zeros(params), zeros(params)
        step = jax.jit(jax.vmap(step_one, in_axes=(0, 0, 0, None, 0)),
                       out_shardings=rows, donate_argnums=(0, 1, 2))
        mix = jax.jit(gossip, out_shardings=rows, donate_argnums=0)
        vocab = int(cfg["vocab_size"])
        trace = []
        for t in range(1, T + 1):
            toks = np.stack([batch_tokens(spec_seed, i, t - 1, vocab, batch,
                                          seq_len) for i in range(n)])
            losses, params, m, v = step(params, m, v,
                                        jnp.asarray(t, jnp.int32),
                                        jax.device_put(toks, rows))
            if communicates(traffic["schedule"], t):
                params = mix(params)
            if t % every == 0:
                trace.append(float(np.mean(np.asarray(losses, np.float64))))
    return {"loss": np.array(trace)}


def readings(traces, reference: dict, traffic: dict) -> dict:
    """The numbers compared, over the traces of every completed run:
    `loss_rel_gap`, the largest relative gap of the mean loss at any trace
    point, and `trace_layout_mismatch`, the runs whose trace points are
    not at eval_every, 2 eval_every, ..., T."""
    layout = list(range(traffic["eval_every"], traffic["T"] + 1,
                        traffic["eval_every"]))
    gap, mismatch = 0.0, 0
    for tr in traces:
        mismatch += list(tr.iters) != layout
        gap = max(gap, check.rel_gap(tr.fvals, reference["loss"]))
    return {"loss_rel_gap": gap, "trace_layout_mismatch": float(mismatch)}
