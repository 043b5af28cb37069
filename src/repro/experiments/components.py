"""Component registries: problems, topologies, schedules, stepsizes.

Each registry maps a string kind + JSON-able kwargs (exactly what a
`ComponentSpec` carries) to a built component. Problems bundle BOTH
execution styles -- per-node numpy closures for the event-driven netsim and
stacked jax closures for the dense simulator -- so one spec runs unchanged
on every backend that can host its problem class.

Bit-identity note: the numpy closures here are the exact code previously
inlined in `benchmarks/fig_async.py` / `netsim.problems`, moved -- not
rewritten -- so the migrated benchmark drivers reproduce their pre-redesign
seeded traces bit-for-bit (gated in tests/test_experiments_migration.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import numpy as np

from repro.core import graphs as _graphs
from repro.core import schedules as _sched
from repro.core.dda import stepsize_sqrt
from repro.data.pipeline import metric_learning_pairs
from repro.experiments.registry import Registry
from repro.netsim.problems import quadratic_consensus as _quadratic

__all__ = [
    "Problem",
    "LMProblem",
    "problems",
    "topologies",
    "schedules",
    "stepsizes",
]

problems = Registry("problem")
topologies = Registry("topology")
schedules = Registry("schedule")
stepsizes = Registry("stepsize")


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Problem:
    """One distributed problem instance, in both execution styles.

    netsim/dense front halves:
      grad_fn:       per-node numpy `(i, x_i, t) -> g` (NetSimulator).
      eval_fn:       numpy `x -> float` full objective (NetSimulator).
      subgrad_stack: jax `(x_stack, t, key) -> g_stack` (DDASimulator).
      objective:     jax `x -> scalar` full objective (DDASimulator).
      projection:    optional stacked Proj_X for constrained problems
                     (jax; applied by DDASimulator after the prox step).

    `fstar_fn` computes (or looks up) the centralized optimum F*; it can be
    expensive (subgradient descent for the non-smooth problem), so it is
    called lazily and cached by `fstar`.
    """

    name: str
    n: int
    d: int
    grad_fn: Callable[[int, np.ndarray, int], np.ndarray]
    eval_fn: Callable[[np.ndarray], float]
    subgrad_stack: Callable | None = None
    objective: Callable | None = None
    projection: Callable | None = None
    fstar_fn: Callable[[], float] | None = None
    _fstar: float | None = dataclasses.field(default=None, repr=False)

    @property
    def fstar(self) -> float:
        if self._fstar is None:
            if self.fstar_fn is None:
                raise ValueError(f"problem {self.name!r} has no known F*")
            self._fstar = float(self.fstar_fn())
        return self._fstar

    def f0(self) -> float:
        """F at the canonical start x0 = 0."""
        return float(self.eval_fn(np.zeros(self.d)))

    def eps_value(self, eps_frac: float) -> float:
        """Accuracy target F* + eps_frac * (F(0) - F*)."""
        return self.fstar + float(eps_frac) * (self.f0() - self.fstar)


@dataclasses.dataclass(frozen=True)
class LMProblem:
    """Marker problem for the `launch` backend: the 'problem' is consensus
    data-parallel LM training of a registry architecture, not a convex
    objective -- dense/netsim backends reject it."""

    arch: str
    variant: str = "smoke"
    batch_per_node: int = 8
    seq_len: int = 64


@problems.register("quadratic_consensus", aliases=("quadratic",))
def _quadratic_problem(n: int, d: int, seed: int = 0,
                       batchable: bool = False) -> Problem:
    """`netsim.problems.quadratic_consensus` plus its dense jax half.
    `batchable` selects the eval form exactly as the netsim tests/bench do
    (the non-batchable form is what fig_adaptive's seeded traces used)."""
    centers, grad_fn, eval_fn = _quadratic(n, d, seed=seed,
                                           batchable=batchable)
    cbar = centers.mean(axis=0)
    spread = float(np.mean(np.sum(centers ** 2, axis=1))
                   - np.sum(cbar ** 2))

    import jax.numpy as jnp
    centers_j = jnp.asarray(centers)
    cbar_j = jnp.asarray(cbar)

    def subgrad_stack(x_stack, t, key):
        return 2.0 * (x_stack - centers_j)

    def objective(x):
        return jnp.sum((x - cbar_j) ** 2) + spread

    return Problem(name="quadratic_consensus", n=n, d=d,
                   grad_fn=grad_fn, eval_fn=eval_fn,
                   subgrad_stack=subgrad_stack, objective=objective,
                   fstar_fn=lambda: float(eval_fn(centers.mean(axis=0))))


def nonsmooth_centers(n: int, M: int, d: int, seed: int) -> np.ndarray:
    """The registry nonsmooth problem's center tensor (n, M, 2, d). Public
    so drivers that need problem GEOMETRY (fig2's R_est radius estimate)
    read the exact centers the problem optimizes instead of regenerating
    with their own copy of the center_scale constant."""
    from repro.data.pipeline import nonsmooth_quadratic_problem
    return nonsmooth_quadratic_problem(n, M, d, seed,
                                       center_scale=1.5).astype(np.float64)


def nonsmooth_centralized_optimum(centers: np.ndarray,
                                  iters: int = 800) -> float:
    """Reference F* via centralized subgradient descent on the mean
    objective (moved verbatim from benchmarks/fig_async.py; mirrors
    NonsmoothQuadratics.optimum_value)."""
    n, M, _, d = centers.shape

    def full_grad(x):
        diff = x[None, None, None, :] - centers
        q = np.sum(diff * diff, axis=-1)
        pick = np.argmax(q, axis=-1)
        chosen = np.take_along_axis(diff, pick[..., None, None],
                                    axis=2)[:, :, 0]
        return 2.0 * np.sum(chosen, axis=(0, 1)) / n

    def value(x):
        diff = x[None, None, None, :] - centers
        q = np.sum(diff * diff, axis=-1)
        return float(np.mean(np.sum(np.max(q, axis=-1), axis=-1)))

    x = np.zeros(d)
    best = value(x)
    lr0 = 1.0 / (4.0 * M)
    for t in range(1, iters + 1):
        x = x - (lr0 / math.sqrt(t)) * full_grad(x)
        if t % 50 == 0:
            best = min(best, value(x))
    return best


@problems.register("nonsmooth")
def _nonsmooth_problem(n: int, M: int = 30, d: int = 20,
                       seed: int = 0) -> Problem:
    """Paper section V.B non-smooth quadratics, f_i = sum_j max(l1, l2).
    Numpy closures moved verbatim from benchmarks/fig_async.build_problem;
    the jax half mirrors benchmarks/paper_problems.NonsmoothQuadratics."""
    centers = nonsmooth_centers(n, M, d, seed)

    def grad_fn(i, x, t):
        diff = x[None, None, :] - centers[i]          # (M, 2, d)
        q = np.sum(diff * diff, axis=-1)              # (M, 2)
        pick = np.argmax(q, axis=-1)                  # (M,)
        chosen = np.take_along_axis(
            diff, pick[:, None, None], axis=1)[:, 0]  # (M, d)
        return 2.0 * np.sum(chosen, axis=0)

    def eval_fn(x):
        diff = x[None, None, None, :] - centers       # (n, M, 2, d)
        q = np.sum(diff * diff, axis=-1)
        return float(np.mean(np.sum(np.max(q, axis=-1), axis=-1)))

    import jax.numpy as jnp
    from repro.kernels import ops as _kops
    centers_j = jnp.asarray(centers)
    # a second device copy in the Pallas kernel's layout, where it runs
    centers_k = _kops.nonsmooth_kernel_layout(centers_j)
    # the objective's split distances read the centres as one (2nM, d)
    # matrix, kept as such: a TPU tiles the (n, M, 2, d) array by its pair
    # axis, and a matmul over it would copy it into rows first
    centers_sq = jnp.sum(centers_j * centers_j, axis=-1)
    centers_rows = centers_j.reshape(n * M * 2, d)

    def subgrad_stack(x_stack, t, key):
        return _kops.nonsmooth_subgrad_impl(
            x_stack, centers_rows.reshape(n, M, 2, d), centers_k)

    def objective(x):
        return _kops.nonsmooth_objective(x, centers_rows, centers_sq)

    return Problem(name="nonsmooth", n=n, d=d, grad_fn=grad_fn,
                   eval_fn=eval_fn, subgrad_stack=subgrad_stack,
                   objective=objective,
                   fstar_fn=lambda: nonsmooth_centralized_optimum(centers))


@problems.register("least_squares")
def _least_squares_problem(n: int, d: int = 64, m_per_node: int = 200,
                           seed: int = 0) -> Problem:
    """Node-specific least squares (the quickstart problem): f_i(x) =
    ||A_i x - b_i||^2 with per-node solutions, so consensus is required."""
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, m_per_node, d)) / np.sqrt(d)
    x_true = rng.normal(size=(d,))
    b = np.einsum("nmd,d->nm", A, x_true) + rng.normal(
        scale=0.1 + 0.5 * rng.random((n, 1)), size=(n, m_per_node))

    def grad_fn(i, x, t):
        res = A[i] @ x - b[i]
        return 2.0 * (A[i].T @ res)

    def eval_fn(x):
        res = np.einsum("nmd,d->nm", A, x) - b
        return float(np.mean(np.sum(res * res, axis=1)))

    import jax.numpy as jnp
    A_j, b_j = jnp.asarray(A), jnp.asarray(b)

    def subgrad_stack(x_stack, t, key):
        res = jnp.einsum("nmd,nd->nm", A_j, x_stack) - b_j
        return 2.0 * jnp.einsum("nmd,nm->nd", A_j, res)

    def objective(x):
        res = jnp.einsum("nmd,d->nm", A_j, x) - b_j
        return jnp.mean(jnp.sum(res * res, axis=1))

    def fstar():
        x_star, *_ = np.linalg.lstsq(A.reshape(n * m_per_node, d),
                                     b.reshape(-1), rcond=None)
        return eval_fn(x_star)

    return Problem(name="least_squares", n=n, d=d, grad_fn=grad_fn,
                   eval_fn=eval_fn, subgrad_stack=subgrad_stack,
                   objective=objective, fstar_fn=fstar)


@functools.lru_cache(maxsize=4)
def _metric_pairs_cached(m_pairs: int, d_feat: int, seed: int):
    """The pair set is independent of the node count, but the runner's
    problem cache keys on n -- without this, a fig1-style n sweep would
    regenerate the (2 m_pairs, d) synthetic dataset once per cell."""
    return metric_learning_pairs(m_pairs, d_feat, seed)


@problems.register("metric_learning")
def _metric_learning_problem(n: int, m_pairs: int = 2000, d_feat: int = 8,
                             seed: int = 0) -> Problem:
    """Paper section V.A metric learning: x = [vec(A) | b], hinge losses
    s_j * (dist_A(u_j, v_j) - b) + 1 over similar/dissimilar pairs, with
    Proj onto {A PSD, b >= 1}. The jax half mirrors
    benchmarks/paper_problems.MetricLearning (the fig1 driver's problem,
    now spec-addressable); pairs come from the same
    `data.pipeline.metric_learning_pairs` generator. The state dimension is
    d_feat^2 + 1 -- the paper's quadratic-in-d message-size regime. No
    closed-form F*, so eps targets must come from the driver (fig1 uses a
    fraction of F(0)).
    """
    u_np, v_np, s_np = _metric_pairs_cached(m_pairs, d_feat, seed)
    dim = d_feat * d_feat + 1
    base = m_pairs // n
    slices = [slice(i * base, (i + 1) * base) for i in range(n)]

    def _split_np(x):
        return x[:d_feat * d_feat].reshape(d_feat, d_feat), x[d_feat * d_feat]

    def grad_fn(i, x, t):
        A, b = _split_np(x)
        u, v, s = u_np[slices[i]], v_np[slices[i]], s_np[slices[i]]
        diff = u - v
        dist2 = np.einsum("md,de,me->m", diff, A, diff)
        w = np.where(s * (dist2 - b) + 1.0 > 0.0, s, 0.0)
        gA = np.einsum("m,md,me->de", w, diff, diff)
        return np.concatenate([gA.reshape(-1), [-np.sum(w)]])

    def eval_fn(x):
        A, b = _split_np(np.asarray(x))
        diff = u_np - v_np
        dist2 = np.einsum("md,de,me->m", diff, A, diff)
        return float(np.sum(np.maximum(0.0, s_np * (dist2 - b) + 1.0)))

    import jax
    import jax.numpy as jnp
    u_j, v_j, s_j = jnp.asarray(u_np), jnp.asarray(v_np), jnp.asarray(s_np)
    us = jnp.stack([u_j[sl] for sl in slices])
    vs = jnp.stack([v_j[sl] for sl in slices])
    ss = jnp.stack([s_j[sl] for sl in slices])

    def _split(x):
        return x[:d_feat * d_feat].reshape(d_feat, d_feat), x[d_feat * d_feat]

    def node_grad(x, u, v, s):
        A, b = _split(x)
        diff = u - v
        dist2 = jnp.einsum("md,de,me->m", diff, A, diff)
        w = jnp.where((s * (dist2 - b) + 1.0) > 0.0, s, 0.0)
        gA = jnp.einsum("m,md,me->de", w, diff, diff)
        return jnp.concatenate([gA.reshape(-1), -jnp.sum(w)[None]])

    def subgrad_stack(x_stack, t, key):
        return jax.vmap(node_grad)(x_stack, us, vs, ss)

    def objective(x):
        A, b = _split(x)
        diff = u_j - v_j
        dist2 = jnp.einsum("md,de,me->m", diff, A, diff)
        return jnp.sum(jnp.maximum(0.0, s_j * (dist2 - b) + 1.0))

    def _proj_one(x):
        A, b = _split(x)
        A = 0.5 * (A + A.T)
        evals, evecs = jnp.linalg.eigh(A)
        A = (evecs * jnp.maximum(evals, 0.0)) @ evecs.T
        return jnp.concatenate([A.reshape(-1),
                                jnp.maximum(b, 1.0)[None]])

    def projection(x_stack):
        return jax.vmap(_proj_one)(x_stack)

    return Problem(name="metric_learning", n=n, d=dim, grad_fn=grad_fn,
                   eval_fn=eval_fn, subgrad_stack=subgrad_stack,
                   objective=objective, projection=projection)


@problems.register("lm")
def _lm_problem(arch: str, variant: str = "smoke", batch_per_node: int = 8,
                seq_len: int = 64) -> LMProblem:
    return LMProblem(arch=arch, variant=variant,
                     batch_per_node=batch_per_node, seq_len=seq_len)


# ---------------------------------------------------------------------------
# topologies (n comes from the problem; params carry the shape knobs)
# ---------------------------------------------------------------------------


@topologies.register("complete")
def _complete(n: int) -> _graphs.CommGraph:
    return _graphs.complete_graph(n)


@topologies.register("ring")
def _ring(n: int) -> _graphs.CommGraph:
    return _graphs.ring_graph(n)


@topologies.register("torus")
def _torus(n: int) -> _graphs.CommGraph:
    return _graphs.torus_graph(n)


@topologies.register("hypercube")
def _hypercube(n: int) -> _graphs.CommGraph:
    return _graphs.hypercube_graph(n)


@topologies.register("expander")
def _expander(n: int, k: int = 4, seed: int = 0) -> _graphs.CommGraph:
    return _graphs.kregular_expander(n, k=k, seed=seed)


@topologies.register("rregular")
def _rregular(n: int, k: int = 4, seed: int = 0) -> _graphs.CommGraph:
    return _graphs.random_regular_expander(n, k=k, seed=seed)


@topologies.register("expander_sequence")
def _expander_seq(n: int, k: int = 4, length: int = 4,
                  seed: int = 0) -> _graphs.GraphSequence:
    return _graphs.expander_sequence(n, k=k, length=length, seed=seed)


# ---------------------------------------------------------------------------
# schedules (the registry `core.schedules.make_schedule` now routes through)
# ---------------------------------------------------------------------------


@schedules.register("every", aliases=("h1",))
def _every() -> _sched.CommSchedule:
    return _sched.EveryIteration()


@schedules.register("periodic")
def _periodic(h: int = 1) -> _sched.CommSchedule:
    return _sched.Periodic(h=h)


@schedules.register("sparse")
def _sparse(p: float = 0.3) -> _sched.CommSchedule:
    return _sched.IncreasinglySparse(p=p)


@schedules.register("piecewise")
def _piecewise(h: int = 1) -> _sched.CommSchedule:
    return _sched.PiecewisePeriodic(h=h)


@schedules.register("adaptive")
def _adaptive(h0: int = 1, p: float = 0.0, h_max: int = 512):
    from repro.adaptive.schedule import AdaptiveSchedule
    return AdaptiveSchedule(h0=h0, p=p, h_max=h_max)


# ---------------------------------------------------------------------------
# stepsizes
# ---------------------------------------------------------------------------


@stepsizes.register("sqrt")
def _sqrt(A: float = 1.0, q: float = 0.5) -> Callable:
    """a(t) = A / max(t, 1)^q -- `core.dda.stepsize_sqrt`, the canonical
    jax/numpy-generic default shared by every execution mode."""
    return stepsize_sqrt(A, q)


@stepsizes.register("inv_sqrt")
def _inv_sqrt(A: float = 1.0) -> Callable:
    """a(t) = A / sqrt(max(t, 1)) via `math.sqrt` on host floats -- the
    exact closure the netsim benchmarks historically inlined (kept distinct
    from "sqrt" because `x ** 0.5` and `math.sqrt(x)` are not guaranteed
    bit-equal, and the migration gate compares traces bitwise). Host-only:
    not traceable, so the dense backend rejects it."""
    def a(t):
        return A / math.sqrt(max(t, 1.0))
    return a


def build_component(registry: Registry, kind: str,
                    params: dict[str, Any], **extra: Any) -> Any:
    """Build `kind` from `registry` with spec params plus runner-provided
    context (e.g. the problem's n for topologies). Spec params win conflicts
    loudly: a manifest must not silently override runner context."""
    clash = set(params) & set(extra)
    if clash:
        raise ValueError(
            f"{registry.kind} {kind!r} params {sorted(clash)} are "
            f"runner-provided and cannot be set in the spec")
    return registry.build(kind, **params, **extra)
