"""Distributed Dual Averaging (DDA) -- the paper's algorithm (eq. 3-5).

Per node i, at iteration t (1-indexed):

    z_i(t)   = sum_j p_ij z_j(t-1) + g_i(t-1)         (consensus + subgradient)
    x_i(t)   = argmin_x { <z_i(t), x> + psi(x)/a(t) } (proximal step)
    xhat_i(t)= ((t-1) xhat_i(t-1) + x_i(t)) / t       (running average)

with psi(x) = 0.5 ||x||^2 the proximal step is x = Proj_X(-a(t) z) (paper V.A).
On cheap iterations (no communication) the consensus sum is replaced by
z_i(t) = z_i(t-1) + g_i(t-1)  (paper IV.A).

Two execution modes:

  * `DDASimulator` -- stacked (n, ...) arrays on one device. Mixing is the
    dense P matmul oracle or, for k-regular graphs, the sparse fast path
    (neighbor-index gather + the fused `kernels.ops.gossip_gather_mix`
    accumulation, O(nkd) instead of O(n^2 d)); the whole run executes as
    ONE compiled scan over precomputed comm-mask data (see `run`), with
    `run_batch` vmapping sweep lanes. Bit-faithful to the paper's
    algorithm; used for the paper's experiments (benchmarks/fig*) and as
    the oracle for the distributed mode.
  * `dda_local_step` / `dda_mix_step` -- per-shard pytree updates with
    `mix_collective` over a mesh axis, used by the production launcher. Both
    are pure and jit/shard_map friendly; the schedule (which step type to run)
    is decided by the host launcher, never by traced control flow, so each
    variant compiles to a collective-free / collective-bearing program
    respectively.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as _cons
from repro.core.graphs import CommGraph
from repro.core.schedules import CommSchedule, EveryIteration

__all__ = [
    "DDAState",
    "dda_init",
    "dda_local_step",
    "dda_mix_step",
    "DDASimulator",
    "SimTrace",
    "TRACE_FIELDS",
    "json_sanitize",
    "stepsize_sqrt",
    "trace_time_to_reach",
]

PyTree = Any


def stepsize_sqrt(A: float, q: float = 0.5) -> Callable[[jax.Array], jax.Array]:
    """a(t) = A / t^q (paper uses q=1/2 for bounded/periodic schedules and
    general q in (p, 1) for increasingly sparse ones).

    The one canonical definition of the default schedule, shared by every
    execution mode: the dense `DDASimulator` calls it with a traced float32
    scalar inside jit (jnp path), while `repro.netsim`'s event-driven nodes
    call it with host floats / float64 numpy batches (np path, full
    precision). Sharing the closure keeps stepsize sweeps comparable across
    modes -- a re-implemented inline lambda in one mode could silently
    diverge from the other.
    """
    def a(t):
        xp = jnp if isinstance(t, jax.Array) else np
        return A / xp.maximum(t, 1.0) ** q
    return a


class DDAState(NamedTuple):
    z: PyTree      # accumulated dual (subgradient) direction
    x: PyTree      # current primal iterate
    xhat: PyTree   # running average (the algorithm's output)
    t: jax.Array   # iteration counter (float32 scalar for stable division)


def dda_init(x0: PyTree) -> DDAState:
    zeros = jax.tree.map(jnp.zeros_like, x0)
    return DDAState(z=zeros, x=x0, xhat=x0, t=jnp.asarray(0.0, jnp.float32))


def _prox(z: PyTree, a_t: jax.Array, projection: Callable[[PyTree], PyTree] | None) -> PyTree:
    x = jax.tree.map(lambda zl: (-a_t * zl).astype(zl.dtype), z)
    return projection(x) if projection is not None else x


def _advance(state: DDAState, z_new: PyTree, a_fn, projection) -> DDAState:
    t_new = state.t + 1.0
    x_new = _prox(z_new, a_fn(t_new), projection)
    xhat_new = jax.tree.map(
        lambda h, x: (state.t * h + x) / t_new, state.xhat, x_new)
    return DDAState(z=z_new, x=x_new, xhat=xhat_new, t=t_new)


def dda_local_step(state: DDAState, grad: PyTree, a_fn,
                   projection: Callable | None = None) -> DDAState:
    """Cheap iteration: z <- z + g (no communication)."""
    z_new = jax.tree.map(jnp.add, state.z, grad)
    return _advance(state, z_new, a_fn, projection)


def dda_mix_step(state: DDAState, grad: PyTree, graph: CommGraph,
                 axis_name: str, a_fn,
                 projection: Callable | None = None) -> DDAState:
    """Expensive iteration: z <- P z + g (consensus + subgradient).

    Must be called inside shard_map with `axis_name` mapping the consensus
    axis (one DDA node per index).
    """
    mixed = _cons.tree_mix_collective(state.z, graph, axis_name)
    z_new = jax.tree.map(jnp.add, mixed, grad)
    return _advance(state, z_new, a_fn, projection)


# ---------------------------------------------------------------------------
# Single-process simulator (paper-faithful; stacked node dimension)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SimTrace:
    """Evaluation trace with the paper's simulated time model attached."""

    iters: list[int]
    sim_time: list[float]       # cumulative time units: sum of 1/n + k r 1{comm}
    fvals: list[float]          # Fbar(t) = (1/n) sum_i F(xhat_i) (paper Fig 1/2)
    comms: list[int]            # cumulative communication rounds H_t
    disagreement: list[float]   # max_i ||z_i - z_bar||
    fvals_consensus: list[float] = dataclasses.field(default_factory=list)
    # F at the consensus average xhat_bar (not what the paper plots, but
    # useful to separate optimization error from network disagreement)


#: the canonical field list, derived from the dataclass so engine-equality
#: assertions and benchmark writers can never drift from SimTrace itself
TRACE_FIELDS = tuple(f.name for f in dataclasses.fields(SimTrace))


def json_sanitize(obj):
    """Strict-RFC JSON sanitizer for trace/result payloads: np scalars ->
    Python numbers, inf/nan -> null. A diverged or never-reached-target run
    is a legal result (tta = inf, blown-up fvals), and the files carrying
    it -- benchmark --out JSON, the convergence tier's failed-run artifacts
    -- must stay readable by jq/JSON.parse, which reject Infinity/NaN."""
    if isinstance(obj, dict):
        return {k: json_sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_sanitize(v) for v in obj]
    if isinstance(obj, (float, np.floating)):
        v = float(obj)
        return v if math.isfinite(v) else None
    if isinstance(obj, np.integer):
        return int(obj)
    return obj


def trace_time_to_reach(trace: SimTrace, eps_value: float,
                        use_consensus: bool = False) -> float:
    """First simulated time at which the objective reaches eps_value.

    Default (`use_consensus=False`) scans `trace.fvals`, i.e.
    Fbar(t) = (1/n) sum_i F(xhat_i) -- the per-node mean the paper's
    Fig. 1/2 time-to-accuracy curves are read from. Pass
    `use_consensus=True` to instead scan `trace.fvals_consensus`
    (F evaluated at the consensus average xhat_bar), which isolates
    optimization error from network disagreement. Shared by DDASimulator
    (simulated time axis) and netsim.NetSimulator (event-clock axis).
    """
    fvals = trace.fvals_consensus if use_consensus else trace.fvals
    for tt, fv in zip(trace.sim_time, fvals):
        if fv <= eps_value:
            return tt
    return float("inf")


class _HoistedProgram:
    """`jitfn` compiled at `args` with its closed-over arrays -- the
    problem data, the graph's index and weight tables -- passed to the
    executable as arguments instead of baked into it as constants.

    Baked in, the paper's non-smooth problem at n=256, M=30, d=4096 puts
    its 252 MB center tensor into the program several times over: about a
    minute of compile per program on a TPU host, and a 1.6 GB executable
    that no persistent compile cache of ordinary size admits. Passed as
    arguments, the program is the same computation on the same values.

    `name` names the compiled module (`jit_<name>`) and the root of every
    op's name stack, so that a device trace says which program ran."""

    def __init__(self, jitfn, args: tuple, name: str):
        traced = jitfn.trace(*args)
        self._consts = list(traced.jaxpr.consts)
        self._out_tree = traced.out_tree
        jaxpr = traced.jaxpr.jaxpr

        def program(consts, *args):
            return jax.core.eval_jaxpr(jaxpr, consts, *args)

        program.__name__ = program.__qualname__ = name
        self._exe = jax.jit(program).lower(
            self._consts, *jax.tree_util.tree_leaves(args)).compile()

    def __call__(self, *args):
        out = self._exe(self._consts, *jax.tree_util.tree_leaves(args))
        return jax.tree_util.tree_unflatten(self._out_tree, out)

    def as_text(self) -> str:
        """HLO text of the compiled executable."""
        return self._exe.as_text()


def _program_name(kind: tuple) -> str:
    """The compiled module's name for a program kind, e.g. ("scan", True)
    -> `dda_scan_allcomm`, ("vmap", False) -> `dda_vmap_cond`."""
    name = "dda_" + kind[0]
    if len(kind) > 1:
        name += "_allcomm" if kind[1] else "_cond"
    return name


class DDASimulator:
    """Runs DDA with n nodes as a stacked leading axis on one device.

    The traced programs name their layers with `jax.named_scope`s, which
    change only op metadata and which a device trace keeps per op:
    `dda.subgrad`, `dda.mix` (holding `dda.compress`, the compressor's
    own work), `dda.update` (holding `dda.project`) and `dda.evaluate`.
    Each compiled program is named for its kind (`dda_scan_allcomm`,
    `dda_scan_cond`, `dda_vmap_*`, `dda_segment`).

    Args:
      subgrad_fn: (x_stack[n, ...], t) -> g_stack[n, ...]; node i's
        subgradient of f_i at x_i. Deterministic (batch) or stochastic.
      eval_fn: x[...] -> scalar F(x) on the FULL objective. Must be
        jax-traceable: the default scanned loop evaluates the trace
        device-side (use `run(..., loop="segment")` for a host-only
        eval_fn).
      graph: communication topology (mixing matrix P taken from it).
      schedule: communication schedule (every / periodic-h / sparse-p).
      a_fn: stepsize a(t).
      projection: optional Proj_X applied after the prox step (stacked).
      r: communication/computation tradeoff for the simulated time axis.
      compression: a built `repro.compress.Compressor` (or None). The
        transmitted messages are compressed with error feedback kept in
        the scanned carry; sparsifiers (`topk`/`randk`) ride the fused
        compress-mix Pallas pass on the sparse path, quantizers ship a
        dequantized message stack through the same gather. The diagonal
        always mixes the node's exact own z -- only RECEIVED messages are
        compressed. `self.wire_ratio(d)` exposes the byte model for the
        effective tradeoff r -> r*c.
      compress_keep: legacy alias ([beyond paper], kept for back-compat):
        `compress_keep=f` is exactly `compression=TopK(keep=f)`. Mutually
        exclusive with `compression`.
      mix: "auto" | "dense" | "sparse" mixing realization. "dense" is the
        P @ z matmul oracle (the seed path; O(n^2 d)). "sparse" is the
        k-regular fast path: a neighbor-index gather + the fused
        `kernels.ops.gossip_gather_mix` accumulation (O(n k d)) -- the
        paper's degree-scaling communication argument applied to the
        simulator's own memory traffic. "auto" picks sparse whenever the
        graph's permutation edge set is materially sparser than complete
        (k + 1 < n) and any `mix_weights` override is supported on the
        edge set; it falls back to dense otherwise (the resolved choice
        is exposed as `self.mix_mode`). Compression no longer disqualifies
        the sparse path: compressed messages ride the fused compress-mix
        kernel (`kernels.ops.compress_mix`) there.
      mix_weights: optional (n, n) mixing-matrix override (e.g. the
        straggler-reweighted effective P from
        `AdaptiveController(reweight_gossip=True)`). The sparse path folds
        it into per-edge weight vectors (slot weight W[i, src] /
        multiplicity, the netsim engines' convention); a matrix with
        weight OUTSIDE the graph's edge-plus-diagonal support cannot be
        gathered along edges, so it automatically falls back to the dense
        matmul ("non-regular" in the kernel's sense).
    """

    def __init__(self, subgrad_fn, eval_fn, graph: CommGraph,
                 schedule: CommSchedule | None = None,
                 a_fn=None, projection=None, r: float = 0.0,
                 compress_keep: float | None = None,
                 mix: str = "auto",
                 mix_weights: np.ndarray | None = None,
                 compression=None):
        self.subgrad_fn = subgrad_fn
        self.eval_fn = eval_fn
        self.graph = graph
        self.schedule = schedule or EveryIteration()
        self.a_fn = a_fn or stepsize_sqrt(1.0)
        self.projection = projection
        self.r = float(r)
        if compress_keep is not None and compression is not None:
            raise ValueError("pass either compression or the legacy "
                             "compress_keep alias, not both")
        if compress_keep is not None:
            from repro.compress import TopK
            compression = TopK(keep=float(compress_keep))
        self.compress_keep = compress_keep
        # "none" normalizes to no compression so the uncompressed program
        # (and its compile cache keys) is byte-for-byte the seed program
        if compression is not None and compression.kind == "none":
            compression = None
        self.compression = compression
        self.mix_weights = (None if mix_weights is None
                            else np.asarray(mix_weights, np.float64))
        self.mix_mode = self._resolve_mix_mode(mix)
        #: per-segment mean per-node error-feedback residual norms of the
        #: last run/run_batch (np (S,) or (B, S)); zeros when uncompressed
        self.last_res_norms: np.ndarray | None = None
        P_host = (self.mix_weights if self.mix_weights is not None
                  else graph.mixing_matrix())
        self._P = jnp.asarray(P_host, jnp.float32)
        # off-diagonal mixing applies to RECEIVED (possibly compressed)
        # messages; the diagonal always uses the node's exact own state.
        self._P_off = self._P - jnp.diag(jnp.diag(self._P))
        self._P_diag = jnp.diag(self._P)
        if self.mix_mode == "sparse":
            S_in, w_self, w_edge = self._sparse_weights()
            self._S_in = jnp.asarray(S_in)
            self._w_self = jnp.asarray(w_self, jnp.float32)
            self._w_edge = jnp.asarray(w_edge, jnp.float32)

        def _mix(z, res, t):
            """One consensus round; messages are compressed (with the
            error-feedback residual `res` folded in and updated) when a
            compressor is attached ([beyond paper], repro.compress; the
            wire ratio c scales the effective tradeoff r -> r*c)."""
            comp = self.compression
            sparse = self.mix_mode == "sparse"
            if sparse:
                from repro.kernels import ops as _kops
            with jax.named_scope("dda.mix"):
                if comp is None:
                    if sparse:
                        return _kops.gossip_gather_mix_impl(
                            z, self._S_in, self._w_self, self._w_edge), res
                    return _cons.mix_dense(z, self._P), res
                with jax.named_scope("dda.compress"):
                    corrected = z + res
                    if sparse and comp.is_sparsifier:
                        mask = comp.support_mask_jax(corrected, t)
                        sent = corrected * mask
                    else:
                        sent = comp.compress_jax(corrected, t)
                    new_res = corrected - sent if comp.error_feedback else res
                if sparse and comp.is_sparsifier:
                    # fused sparsify-mix: the 0/1 support rides the kernel,
                    # never materializing the masked message stack
                    mixed = _kops.compress_mix_impl(
                        z, corrected, mask, self._S_in, self._w_self,
                        self._w_edge)
                elif sparse:
                    mixed = _kops.gossip_gather_mix_impl(
                        z, self._S_in, self._w_self, self._w_edge, msg=sent)
                else:
                    # off-diagonal mixing consumes the TRANSMITTED messages;
                    # the diagonal keeps the node's exact own z
                    mixed = (self._P_diag[:, None] * z
                             + _cons.mix_dense(sent, self._P_off))
                return mixed, new_res

        def make_body(always_comm: bool):
            """always_comm=True drops the per-iteration `lax.cond`: the
            host already knows the whole comm mask, and for an all-comm
            window the straight-line mix fuses into the z/x/xhat update
            chain (the cond boundary otherwise forces an extra
            materialization of the mixed z -- ~20% of the iteration on the
            CPU fast path)."""
            def body(carry, inp):
                z, x, xhat, res, t = carry
                comm, key = inp
                with jax.named_scope("dda.subgrad"):
                    g = self.subgrad_fn(x, t, key)
                if always_comm:
                    z_mixed, res_new = _mix(z, res, t)
                else:
                    z_mixed, res_new = jax.lax.cond(
                        comm, _mix, lambda zz, rr, tt: (zz, rr), z, res, t)
                with jax.named_scope("dda.update"):
                    z_new = z_mixed + g
                    t_new = t + 1.0
                    a_t = self.a_fn(t_new)
                    x_new = -a_t * z_new
                    if self.projection is not None:
                        with jax.named_scope("dda.project"):
                            x_new = self.projection(x_new)
                    xhat_new = (t * xhat + x_new) / t_new
                return (z_new, x_new, xhat_new, res_new, t_new), None
            return body

        body = make_body(always_comm=False)

        @jax.jit
        def _segment(z, x, xhat, res, t0, comm_mask, keys):
            """Scan `len(comm_mask)` iterations starting at t0 (0-indexed)."""
            (z, x, xhat, res, t), _ = jax.lax.scan(
                body, (z, x, xhat, res, t0), (comm_mask, keys))
            return z, x, xhat, res, t

        self._segment = _segment

        # a jitted eval_fn would stay a nested call in the scan program,
        # its closed-over problem data baked into the executable where
        # `_HoistedProgram` cannot reach it: trace the function it wraps
        eval_inline = getattr(self.eval_fn, "__wrapped__", self.eval_fn)

        def make_scan_program(always_comm: bool):
            """Whole-run program: scan over evaluation segments, each an
            inner scan over iterations, with the trace statistics computed
            device-side -- ONE dispatch instead of T/eval_every, and the
            unit `run_batch` vmaps over sweep lanes.

            masks: (S, E) comm flags; starts: (S,) segment start iteration
            counts (the legacy per-segment RNG stream is reproduced by
            folding each start into `root`); root: run PRNGKey.
            """
            seg_body = make_body(always_comm)

            def prog(state, masks, starts, root):
                def seg(carry, inp):
                    mask, start = inp
                    keys = jax.random.split(jax.random.fold_in(root, start),
                                            mask.shape[0])
                    carry, _ = jax.lax.scan(seg_body, carry, (mask, keys))
                    z, x, xhat, res, t = carry
                    with jax.named_scope("dda.evaluate"):
                        fv = jnp.mean(jax.vmap(eval_inline)(xhat))
                        fvc = eval_inline(jnp.mean(xhat, axis=0))
                        dis = _cons.disagreement(z)
                        # mean per-node error-feedback residual norm: the
                        # compression block's trajectory (zeros uncompressed)
                        rn = jnp.mean(jnp.sqrt(jnp.sum(
                            res.reshape(res.shape[0], -1) ** 2, axis=-1)))
                    return carry, (fv, fvc, dis, rn)

                return jax.lax.scan(seg, state, (masks, starts))
            return prog

        self._scan_programs = {ac: make_scan_program(ac)
                               for ac in (False, True)}
        self._scan_jits = {ac: jax.jit(p)
                           for ac, p in self._scan_programs.items()}
        self._scan_vmaps: dict[bool, Any] = {}  # built lazily by run_batch
        # AOT compile cache + per-run wall split (see _timed_call): keyed by
        # (program kind, argument shapes/dtypes); `last_timings` is reset at
        # the top of every run/run_batch and read by the experiments runner
        # to populate RunMetrics.compile_s / execute_s.
        self._compiled: dict[tuple, Any] = {}
        self.last_timings: dict[str, float] = {"compile_s": 0.0,
                                               "eval_s": 0.0}
        #: a `repro.obs.Tracer` that a caller binds for one run (the
        #: experiments runner does): compiles, program calls and result
        #: assembly open host spans on it, and so on the profiler's clock
        #: when one runs. None, the default, traces nothing.
        self.tracer = None

    # -- timed dispatch ------------------------------------------------------

    def _reset_timings(self) -> None:
        self.last_timings = {"compile_s": 0.0, "eval_s": 0.0}
        self.last_res_norms = None

    def wire_ratio(self, d: int) -> float:
        """Bytes-on-wire fraction c for a d-float message under the
        attached compressor (1.0 uncompressed) -- the multiplier for the
        paper's effective tradeoff r -> r*c."""
        return (1.0 if self.compression is None
                else self.compression.wire_ratio(int(d)))

    def _span(self, name: str):
        """The bound tracer's host span `name`, or no span when unbound."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name)

    def _get_compiled(self, kind: tuple, jitfn, args: tuple):
        """AOT executable for `jitfn` at these argument shapes, or None when
        `jitfn` has no `.trace` (e.g. a test double swapped in for a jit
        function -- callers then dispatch the object directly).

        The executable (`_HoistedProgram`) computes what the plain jit call
        would, with the closed-over arrays passed as arguments. It is
        cached on (kind, arg shapes/dtypes) and the compile wall charged to
        `last_timings["compile_s"]` exactly once per shape -- which is what
        makes the cache shareable: a long-lived holder of this simulator
        (the serving layer's compile cache, the adaptive chunk loop) pays
        compile once and every later dispatch is pure execute. A miss is
        the `compile` span."""
        if not hasattr(jitfn, "trace"):
            return None
        key = kind + tuple((tuple(leaf.shape), str(leaf.dtype))
                           for leaf in jax.tree_util.tree_leaves(args))
        entry = self._compiled.get(key)
        if entry is None:
            t0 = time.perf_counter()
            with self._span("compile"):
                entry = _HoistedProgram(jitfn, args, _program_name(kind))
            self.last_timings["compile_s"] += time.perf_counter() - t0
            self._compiled[key] = entry
        return entry

    def _timed_call(self, kind: tuple, jitfn, args: tuple):
        """Dispatch a jitted program through the AOT lower/compile path so
        that compile is observable apart from execution (see
        `_get_compiled`). The call is the `execute` span: its `dispatch`
        moves host arguments to the device and returns once the program
        is queued, its `wait` blocks on the results."""
        entry = self._get_compiled(kind, jitfn, args)
        fn = jitfn if entry is None else entry
        with self._span("execute"):
            with self._span("dispatch"):
                out = fn(*args)
            with self._span("wait"):
                return jax.block_until_ready(out)

    # -- mix-mode resolution -------------------------------------------------

    def _resolve_mix_mode(self, mix: str) -> str:
        if mix not in ("auto", "dense", "sparse"):
            raise ValueError(f"mix must be auto/dense/sparse, got {mix!r}")
        if mix == "dense":
            return "dense"
        # NOTE: compression deliberately does NOT appear here anymore --
        # compressed messages ride the fused compress-mix kernel (or the
        # msg= gather for quantizers) on the sparse path.
        reasons = []
        if not self.graph.perms:
            reasons.append("graph has no permutation edge set")
        elif self.graph.degree + 1 >= self.graph.n:
            reasons.append("graph is (near-)complete: the matmul moves "
                           "less memory than a degree-(n-1) gather")
        if self.mix_weights is not None and not self._edge_supported():
            reasons.append("mix_weights has weight outside the graph's "
                           "edge support (non-regular P)")
        if reasons:
            if mix == "sparse":
                raise ValueError("sparse mix unavailable: "
                                 + "; ".join(reasons))
            return "dense"
        return "sparse"

    def _edge_supported(self) -> bool:
        """True if mix_weights only places weight on self-loops + edges."""
        W = self.mix_weights
        n = self.graph.n
        allowed = np.eye(n, dtype=bool)
        for perm in self.graph.perms:
            allowed[np.arange(n), np.asarray(perm)] = True
        return not np.any((W != 0.0) & ~allowed)

    def _sparse_weights(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(S_in, w_self, w_edge) for the gather path. S_in[i, j] is the
        node whose value node i receives in permutation slot j. A
        `mix_weights` override folds through the shared
        `graphs.mix_weight_slots` convention (W[i, src] / multiplicity per
        slot), keeping dense and netsim reweighted gossip comparable."""
        g = self.graph
        S_in = np.stack([np.asarray(p, dtype=np.int64) for p in g.perms],
                        axis=1)  # (n, k)
        if self.mix_weights is None:
            # scalar weights: the op's uniform path scales the SUM of the
            # gathers once instead of broadcasting k weight columns
            return (S_in, np.float32(g.self_weight),
                    np.float32(g.edge_weight))
        from repro.core.graphs import mix_weight_slots
        w_slot, w_self = mix_weight_slots(self.mix_weights, S_in)
        return (S_in, w_self.astype(np.float32),
                w_slot.astype(np.float32))

    # -- run loops -----------------------------------------------------------

    def run(self, x0_stack: jax.Array, T: int, eval_every: int = 25,
            seed: int = 0, loop: str = "scan") -> SimTrace:
        """Run T iterations, evaluating every `eval_every`.

        loop="scan" (default): the whole run is one compiled program per
        distinct segment length (at most two: the full segments and a
        remainder), with the comm pattern precomputed host-side by
        `CommSchedule.comm_mask` and fed as data. loop="segment" keeps the
        legacy host loop -- one dispatch per evaluation segment with the
        trace statistics computed eagerly -- for host-only eval_fns and as
        the seed baseline `benchmarks/bench_dense.py` times against.
        """
        n = self.graph.n
        assert x0_stack.shape[0] == n, "x0 must be stacked (n, ...)"
        if loop == "segment":
            return self._run_segment_loop(x0_stack, T, eval_every, seed)
        if loop != "scan":
            raise ValueError(f"loop must be 'scan' or 'segment', got {loop!r}")
        self._reset_timings()
        # the run's arguments: its comm pattern, initial state and key
        with self._span("dispatch"):
            mask_full = np.asarray(self.schedule.comm_mask(0, T),
                                   dtype=bool)
            ac = bool(mask_full.all())
            state = (jnp.zeros_like(x0_stack), x0_stack, x0_stack,
                     jnp.zeros_like(x0_stack),
                     jnp.asarray(0.0, jnp.float32))
            root = jax.random.PRNGKey(seed)
        prog = self._scan_jits[ac]
        S, rem = divmod(T, eval_every)
        outs = []
        # masks and starts stay on the host: the call moves them
        if S:
            masks = mask_full[:S * eval_every].reshape(S, eval_every)
            starts = np.arange(S, dtype=np.int32) * eval_every
            state, out = self._timed_call(("scan", ac), prog,
                                          (state, masks, starts, root))
            outs.append(out)
        if rem:
            masks = mask_full[S * eval_every:].reshape(1, rem)
            starts = np.array([S * eval_every], dtype=np.int32)
            state, out = self._timed_call(("scan", ac), prog,
                                          (state, masks, starts, root))
            outs.append(out)
        if not outs:  # T == 0: an empty trace, as the legacy loop returns
            return SimTrace([], [], [], [], [])
        with self._span("assemble"):
            fv, fvc, dis, rn = (np.concatenate([np.asarray(o[i])
                                                for o in outs])
                                for i in range(4))
            self.last_res_norms = rn
            # compressed messages are cheaper on the wire: the time axis
            # charges the effective tradeoff r*c (c == 1.0 leaves seeds
            # bit-identical)
            r_eff = self.r * self.wire_ratio(
                int(np.prod(x0_stack.shape[1:])))
            return self._assemble_trace(mask_full, T, eval_every, r_eff,
                                        fv, fvc, dis)

    def _assemble_trace(self, mask_full, T, eval_every, r,
                        fv, fvc, dis) -> SimTrace:
        """Host bookkeeping: the simulated time axis (eq. 9 charges) from
        the precomputed comm mask, accumulated segment-by-segment in the
        exact float order of the legacy loop."""
        n, k = self.graph.n, self.graph.degree
        trace = SimTrace([], [], [], [], [])
        sim_time = 0.0
        comm_total = 0
        done = 0
        idx = 0
        while done < T:
            seg = min(eval_every, T - done)
            n_comm = int(mask_full[done:done + seg].sum())
            done += seg
            comm_total += n_comm
            sim_time += seg * (1.0 / n) + n_comm * k * r
            trace.iters.append(done)
            trace.sim_time.append(sim_time)
            trace.fvals.append(float(fv[idx]))
            trace.fvals_consensus.append(float(fvc[idx]))
            trace.comms.append(comm_total)
            trace.disagreement.append(float(dis[idx]))
            idx += 1
        return trace

    def _run_segment_loop(self, x0_stack, T, eval_every, seed) -> SimTrace:
        self._reset_timings()
        z = jnp.zeros_like(x0_stack)
        x = x0_stack
        xhat = x0_stack
        res = jnp.zeros_like(x0_stack)
        t = jnp.asarray(0.0, jnp.float32)
        n, k = self.graph.n, self.graph.degree
        r_eff = self.r * self.wire_ratio(int(np.prod(x0_stack.shape[1:])))
        trace = SimTrace([], [], [], [], [])
        sim_time = 0.0
        comm_total = 0
        root = jax.random.PRNGKey(seed)

        done = 0
        while done < T:
            seg = min(eval_every, T - done)
            mask = np.array([self.schedule.is_comm_step(done + i + 1)
                             for i in range(seg)])
            keys = jax.random.split(jax.random.fold_in(root, done), seg)
            z, x, xhat, res, t = self._timed_call(
                ("segment",), self._segment,
                (z, x, xhat, res, t, jnp.asarray(mask), keys))
            done += seg
            n_comm = int(mask.sum())
            comm_total += n_comm
            sim_time += seg * (1.0 / n) + n_comm * k * r_eff
            t_eval = time.perf_counter()
            xbar = jnp.mean(xhat, axis=0)
            trace.iters.append(done)
            trace.sim_time.append(sim_time)
            trace.fvals.append(float(jnp.mean(jax.vmap(self.eval_fn)(xhat))))
            trace.fvals_consensus.append(float(self.eval_fn(xbar)))
            trace.comms.append(comm_total)
            trace.disagreement.append(float(_cons.disagreement(z)))
            self.last_timings["eval_s"] += time.perf_counter() - t_eval
        return trace

    def run_batch(self, x0_stack: jax.Array, T: int, eval_every: int,
                  masks: np.ndarray, seeds: Sequence[int],
                  rs: Sequence[float] | None = None) -> list[SimTrace]:
        """Run B independent lanes of this simulator as ONE vmapped program.

        Lanes share the problem closures, graph, stepsize and iteration
        count but may differ in comm pattern (`masks`, shape (B, T) --
        sweep axes like `schedule.params.h` are just data here), RNG stream
        (`seeds`) and time charge (`rs`, host-side only). This is the
        executor behind `repro.experiments.run_sweep(parallel="vmap")`:
        one compile + one batched dispatch for a whole sweep grid instead
        of a compile per cell.
        """
        n = self.graph.n
        assert x0_stack.shape[0] == n, "x0 must be stacked (n, ...)"
        masks = np.asarray(masks, dtype=bool)
        B = masks.shape[0]
        assert masks.shape == (B, T), masks.shape
        assert len(seeds) == B, (len(seeds), B)
        c = self.wire_ratio(int(np.prod(x0_stack.shape[1:])))
        rs = ([self.r * c] * B if rs is None
              else [float(r) * c for r in rs])
        assert len(rs) == B

        self._reset_timings()
        ac = bool(masks.all())
        if ac not in self._scan_vmaps:
            self._scan_vmaps[ac] = jax.jit(jax.vmap(
                self._scan_programs[ac],
                in_axes=((0, 0, 0, 0, 0), 0, None, 0)))
        vprog = self._scan_vmaps[ac]
        tile = lambda a: jnp.broadcast_to(a, (B,) + a.shape)
        state = (tile(jnp.zeros_like(x0_stack)), tile(x0_stack),
                 tile(x0_stack), tile(jnp.zeros_like(x0_stack)),
                 jnp.zeros((B,), jnp.float32))
        roots = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        S, rem = divmod(T, eval_every)
        outs = []
        if S:
            m = jnp.asarray(masks[:, :S * eval_every]
                            .reshape(B, S, eval_every))
            starts = jnp.asarray(np.arange(S, dtype=np.int32) * eval_every)
            state, out = self._timed_call(("vmap", ac), vprog,
                                          (state, m, starts, roots))
            outs.append(out)
        if rem:
            m = jnp.asarray(masks[:, S * eval_every:].reshape(B, 1, rem))
            starts = jnp.asarray(np.array([S * eval_every], dtype=np.int32))
            state, out = self._timed_call(("vmap", ac), vprog,
                                          (state, m, starts, roots))
            outs.append(out)
        if not outs:  # T == 0: empty traces, as the legacy loop returns
            return [SimTrace([], [], [], [], []) for _ in range(B)]
        fv, fvc, dis, rn = (np.concatenate([np.asarray(o[i]) for o in outs],
                                           axis=1) for i in range(4))
        self.last_res_norms = rn
        return [self._assemble_trace(masks[b], T, eval_every, rs[b],
                                     fv[b], fvc[b], dis[b])
                for b in range(B)]

    def time_to_reach(self, trace: SimTrace, eps_value: float,
                      use_consensus: bool = False) -> float:
        """See `trace_time_to_reach` (default reads Fbar, per the paper)."""
        return trace_time_to_reach(trace, eps_value, use_consensus)
