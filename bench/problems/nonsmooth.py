"""The paper's non-smooth quadratics (arXiv:1209.1076, section V.B).

Node i holds M pairs of centers and f_i(x) = sum_m max(||x - c_im1||^2,
||x - c_im2||^2); the objective is F(x) = (1/n) sum_i f_i(x). The centers
are drawn here from the seed the same way the program draws them, so the
reference and the program solve the same instance without sharing an
array: each node's centers scatter (standard deviation `CENTER_NOISE`)
around a node offset of standard deviation `CENTER_SCALE`, drawn in
float64 and rounded to float32. Both are the program's constants, not
settings: another value would make a different instance from the
program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import dda_ref

F32 = 4
#: standard deviation of each node's offset, and of its centers around it
CENTER_SCALE = 1.5
CENTER_NOISE = 0.3


def centers(cfg: dict, seed: int) -> np.ndarray:
    """The (n, M, 2, d) float32 centers of the instance of `seed`."""
    p = cfg["problem"]["params"]
    return _centers(p["n"], p["M"], p["d"], int(seed))


@functools.lru_cache(maxsize=1)
def _centers(n, M, d, seed):
    rng = np.random.default_rng(seed)
    offset = rng.normal(0.0, CENTER_SCALE, (n, 1, 1, d))
    c = rng.normal(0.0, CENTER_NOISE, (n, M, 2, d)) + offset
    return c.astype(np.float32)


def _subgrad(C, x):
    # the larger of each pair's two squared distances is the active piece
    diff = x[:, None, None, :] - C                          # (n, M, 2, d)
    q = jnp.sum(diff * diff, axis=-1)                       # (n, M, 2)
    chosen = jnp.where((q[..., 1] > q[..., 0])[..., None],
                       C[:, :, 1], C[:, :, 0])              # (n, M, d)
    return 2.0 * jnp.sum(x[:, None, :] - chosen, axis=1)


def _objective(C, X):
    def one(x):
        diff = x - C
        q = jnp.sum(diff * diff, axis=-1)
        return jnp.mean(jnp.sum(jnp.max(q, axis=-1), axis=-1))
    return jax.lax.map(one, X)


def reference_problem(cfg: dict, seed: int, dtype) -> dda_ref.Problem:
    p = cfg["problem"]["params"]
    C = jnp.asarray(centers(cfg, seed), dtype)
    return dda_ref.Problem(n=p["n"], dim=p["d"], data=C, subgrad=_subgrad,
                           objective=_objective)


def iteration_work(cfg: dict, traffic: dict) -> tuple[float, float]:
    """(flops, bytes) that one iteration needs at the least, whatever
    implements it. Bytes: every center read once from HBM; at 252 MB the
    centers cannot stay on the chip between iterations, while the nodes'
    state (4 MB an array) can, so it is not counted. Flops: the squared
    distances (3 per center coordinate), the active pieces' sum, the mix
    (2 per received value and the self term) and the update (~6 per
    coordinate)."""
    p = cfg["problem"]["params"]
    n, M, d = p["n"], p["M"], p["d"]
    k = len(cfg["mixing"]["shifts"])
    flops = 3.0 * n * M * 2 * d + n * M * d + 2.0 * (k + 1) * n * d \
        + 6.0 * n * d
    return flops, float(F32 * n * M * 2 * d)
