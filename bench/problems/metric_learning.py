"""The paper's metric learning (arXiv:1209.1076, section V.A).

The state is x = [vec(A) | b] with A a d x d matrix, and each pair
(u_j, v_j) with label s_j = +1 (same class) or -1 costs the hinge
max(0, s_j ((u_j - v_j)^T A (u_j - v_j) - b) + 1). Node i holds the i-th
of n equal slices of the pairs; F is the hinge summed over all pairs,
and X = {A positive semidefinite, b >= 1}.

The pairs are class-clustered synthetic vectors in MNIST's width, drawn
here from the seed the same way the program draws them: `CLASSES` class
centers from N(0, 1), a uniform label per vector, the vector its class
center plus N(0, `CLASS_NOISE`) noise, rounded to float32; consecutive
vectors pair up. Both are the program's constants, not settings.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import dda_ref

CLASSES = 10
CLASS_NOISE = 0.8


def pairs(cfg: dict, seed: int):
    """(u, v, s) of the instance of `seed`, float32."""
    p = cfg["problem"]["params"]
    return _pairs(p["m_pairs"], p["d_feat"], int(seed))


@functools.lru_cache(maxsize=1)
def _pairs(m, d, seed):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 1.0, (CLASSES, d))
    labels = rng.integers(0, CLASSES, 2 * m)
    x = (centers[labels] + rng.normal(0.0, CLASS_NOISE, (2 * m, d))
         ).astype(np.float32)
    s = np.where(labels[0::2] == labels[1::2], 1.0, -1.0).astype(np.float32)
    return x[0::2], x[1::2], s


def _split(x, d):
    return x[:d * d].reshape(d, d), x[d * d]


def _hinge_terms(D, s, x):
    """Each pair's margin s (D^T A D - b) + 1, D the pair's difference."""
    A, b = _split(x, D.shape[1])
    dist2 = jnp.sum((D @ A) * D, axis=1)
    return s * (dist2 - b) + 1.0


def _node_subgrad(D, s, x):
    w = jnp.where(_hinge_terms(D, s, x) > 0.0, s, 0.0)
    gA = (D * w[:, None]).T @ D
    return jnp.concatenate([gA.reshape(-1), -jnp.sum(w)[None]])


def _subgrad(data, x):
    (Dn, sn), _ = data
    return jax.vmap(_node_subgrad)(Dn, sn, x)


def _objective(data, X):
    _, (D, s) = data
    return jax.lax.map(
        lambda x: jnp.sum(jnp.maximum(0.0, _hinge_terms(D, s, x))), X)


def _project_one(x):
    d = int(round((x.shape[0] - 1) ** 0.5))
    A, b = _split(x, d)
    A = 0.5 * (A + A.T)
    evals, evecs = jnp.linalg.eigh(A)
    A = (evecs * jnp.maximum(evals, 0.0)) @ evecs.T
    return jnp.concatenate([A.reshape(-1), jnp.maximum(b, 1.0)[None]])


def reference_problem(cfg: dict, seed: int, dtype) -> dda_ref.Problem:
    p = cfg["problem"]["params"]
    n, m, d = p["n"], p["m_pairs"], p["d_feat"]
    u, v, s = pairs(cfg, seed)
    D = u - v
    per = m // n  # node i holds pairs i*per .. (i+1)*per - 1
    Dn = D[:n * per].reshape(n, per, d)
    sn = s[:n * per].reshape(n, per)
    data = tuple(tuple(jnp.asarray(a, dtype) for a in grp)
                 for grp in ((Dn, sn), (D, s)))
    return dda_ref.Problem(n=n, dim=d * d + 1, data=data, subgrad=_subgrad,
                           objective=_objective,
                           projection=jax.vmap(_project_one))
