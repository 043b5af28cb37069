"""Plain distributed dual averaging, the reference the benchmark checks
the timed solves against.

It follows the paper's equations (arXiv:1209.1076, eq. 3-5) with every
node's state as one row of a stacked array, mixes with the dense mixing
matrix P (a matrix product, never the gossip kernels), and imports
nothing of the program under test. A problem module under
`bench/problems/` supplies the subgradient, the objective and the
projection, written out from the paper and built from data the module
generates itself from the seed.

    z_i(t)    = sum_j P_ij z_j(t-1) + g_i(t-1)
    x_i(t)    = Proj(-a(t) z_i(t)),   a(t) = A / t^q
    xhat_i(t) = ((t-1) xhat_i(t-1) + x_i(t)) / t

With a sparsifying compressor, what a node sends is its corrected state
z + e restricted to the k largest magnitudes; the diagonal of P mixes the
node's exact own z, and the error e keeps what was not sent.

At each evaluation point the reference records what the program's trace
records: the mean over nodes of F at each node's running average, F at
the average of the running averages, and max_i ||z_i - mean_j z_j||.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Problem:
    """What the reference needs of one problem instance.

    data:       the problem's arrays (a pytree), passed to the compiled
                program as arguments so that it holds no large constants
    subgrad:    (data, x (n, dim)) -> g (n, dim), node i's subgradient at x_i
    objective:  (data, X (b, dim)) -> F (b,), the full objective at each row
    projection: x (n, dim) -> x (n, dim), or None when unconstrained
    """

    n: int
    dim: int
    data: object
    subgrad: Callable
    objective: Callable
    projection: Callable | None = None


def mixing_matrix(n: int, shifts, self_weight: float,
                  edge_weight: float) -> np.ndarray:
    """P of a circulant graph: node i receives from i - s for each shift
    s, with the given weights; float64."""
    P = np.eye(n) * self_weight
    rows = np.arange(n)
    for s in shifts:
        P[rows, (rows - int(s)) % n] += edge_weight
    return P


def complete_matrix(n: int) -> np.ndarray:
    """P of the complete graph: every node averages all n states."""
    return np.full((n, n), 1.0 / n)


def topk_mask(x, keep: int):
    """0/1 rows marking each row's `keep` largest magnitudes."""
    idx = jax.lax.top_k(jnp.abs(x), keep)[1]
    return jnp.sum(jax.nn.one_hot(idx, x.shape[1], dtype=x.dtype), axis=1)


def run(problem: Problem, P: np.ndarray, A: float, q: float, T: int,
        eval_every: int, dtype=jnp.float32, topk_keep: int | None = None,
        shifts=None) -> dict[str, np.ndarray]:
    """Run T iterations from x = 0 and return the trace at every
    `eval_every`-th iteration as float64 numpy arrays: `fbar` (mean over
    nodes of F at each node's running average), `fxbar` (F at the
    average of the running averages) and `disagreement`.

    Matrix products take the precision in force where this is called
    (`jax.default_matmul_precision`). With the `shifts` of a circulant P,
    each node adds its neighbours' messages one at a time, in the order
    of `shifts`, in place of the matrix product: the same mix, rounded
    another way."""
    n, dim = problem.n, problem.dim
    rows = np.arange(n)

    def receive(P, msg):
        """sum_j P_ij msg_j over the neighbours j != i."""
        if shifts is None:
            return (P - jnp.diag(jnp.diag(P))) @ msg
        out = jnp.zeros_like(msg)
        for s in shifts:
            w = P[rows, (rows - int(s)) % n]
            out = out + w[:, None] * jnp.roll(msg, int(s), axis=0)
        return out

    def step(data, P, carry):
        z, x, xhat, err, t = carry
        g = problem.subgrad(data, x)
        P_diag = jnp.diag(P)
        if topk_keep is None:
            mixed = P @ z if shifts is None else \
                P_diag[:, None] * z + receive(P, z)
        else:
            corrected = z + err
            sent = corrected * topk_mask(corrected, topk_keep)
            mixed = P_diag[:, None] * z + receive(P, sent)
            err = corrected - sent
        z = (mixed + g).astype(dtype)
        t_new = t + 1.0
        x = (-(A / t_new ** q) * z).astype(dtype)
        if problem.projection is not None:
            x = problem.projection(x)
        xhat = ((t * xhat + x) / t_new).astype(dtype)
        return z, x, xhat, err, t_new

    @jax.jit
    def segment(data, P, carry):
        carry = jax.lax.fori_loop(0, eval_every,
                                  lambda _, c: step(data, P, c), carry)
        z, _, xhat, _, _ = carry
        fbar = jnp.mean(problem.objective(data, xhat).astype(jnp.float32))
        fxbar = problem.objective(data, jnp.mean(xhat, axis=0)[None])[0]
        zc = (z - jnp.mean(z, axis=0)).astype(jnp.float32)
        dis = jnp.max(jnp.sqrt(jnp.sum(zc * zc, axis=1)))
        return carry, (fbar, fxbar.astype(jnp.float32), dis)

    P = jnp.asarray(P, dtype)
    zeros = jnp.zeros((n, dim), dtype)
    # the iteration counter and the stepsize stay float32 in every dtype
    carry = (zeros, zeros, zeros, zeros, jnp.asarray(0.0, jnp.float32))
    out = []
    for _ in range(T // eval_every):
        carry, stats = segment(problem.data, P, carry)
        out.append(stats)
    fbar, fxbar, dis = (np.array([float(o[i]) for o in out]) for i in range(3))
    return {"fbar": fbar, "fxbar": fxbar, "disagreement": dis}
