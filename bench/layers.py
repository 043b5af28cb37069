"""The layers that the program names itself in the trace: the named
scopes of the dense simulator's programs (`dda.*`) and the host spans of
its runs (`repro.*`).

- Each device op carries its JAX name stack in the trace's event
  metadata (`bench/xplane.py`). An op belongs to the innermost `dda.*`
  scope of its stack; a fusion carries the stack of its root instruction,
  so a fusion belongs to the scope of its root. The scopes:
  `dda.subgrad` (the problem's subgradient), `dda.mix` (the consensus
  round: the neighbour gather, the Pallas gossip and compress-mix
  kernels, the dense matmul), `dda.compress` (inside the mix: top-k
  selection and mask, compressed messages, error feedback), `dda.update`
  (the dual and primal updates), `dda.project` (inside the update: a
  problem's projection), `dda.evaluate` (the trace statistics).
- The program's `repro.Tracer` spans are profiler annotations on the host
  plane, on the clock of the device ops: `repro.build`, `repro.compile`,
  `repro.execute` holding `repro.dispatch` and `repro.wait`, and
  `repro.assemble`. An idle gap of the device inside a solve is named by
  the innermost of them that covers its midpoint.

`bench/trace.py` reads the trace through `jax.profiler.ProfileData`,
which drops the metadata's stats; this module reads the same file again
and matches its ops to the `Context`'s by name and start. The harness
keeps the trace in a directory of its own, `bench_trace_*` in the
temporary directory, while the readers run: `of(ctx)` finds the file
there whose ops are the context's. Where the trace holds no scope or
span of the program's, as a program that sets none, the functions here
return None.
"""

from __future__ import annotations

import glob
import os
import tempfile
import weakref

from bench import trace, xplane

#: the prefix of the program's named scopes and of its host spans
SCOPE, SPAN = "dda.", "repro."
#: the program's span of a blocked wait for its results
WAIT = "repro.wait"

_found: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def scope_of(name_stack: str) -> str:
    """The innermost `dda.*` scope of a name stack, else ""."""
    for part in reversed(name_stack.split("/")):
        if part.startswith(SCOPE):
            return part.split(":")[0]
    return ""


def compiler_made(name_stack: str) -> bool:
    """An op that no line of the program traced: one with no stack, or
    with the stack of the loop it runs in, which the trace gives an op
    that the compiler made without metadata of its own (on the TPU,
    top-k's mask scatter and the async copies that stage the centres),
    and the loop op itself."""
    return name_stack.split(":")[0].rsplit("/", 1)[-1] in ("", "while")


class Layers:
    """The name stack of each of a trace's device ops, by (instruction
    name, start), and the program's host spans, (name, start, end)."""

    def __init__(self, stacks: dict[tuple, str], spans: list[tuple]):
        self.stacks = stacks
        self.spans = sorted(spans, key=lambda s: s[1])

    @classmethod
    def read(cls, path: str) -> "Layers":
        """The layers of the trace file at `path`."""
        stacks, spans = {}, []
        for plane, lines in xplane.read(path).items():
            if plane.startswith("/device:"):
                for e in lines.get(trace.OPS_LINE, []):
                    name = trace.parse_instruction(e.name)[0]
                    stacks[(name, e.start)] = e.tf_op
            elif plane.startswith("/host:"):
                spans += [(e.name, e.start, e.end)
                          for events in lines.values() for e in events
                          if e.name.startswith(SPAN)]
        return cls(stacks, spans)

    def holds(self, ops) -> bool:
        return all((o.name, o.start) in self.stacks for o in ops)

    def stack(self, op) -> str:
        return self.stacks.get((op.name, op.start), "")

    def scope(self, op) -> str:
        return scope_of(self.stack(op))

    def span_at(self, t: float) -> str | None:
        """The innermost program span that covers time `t`."""
        covering = [s for s in self.spans if s[1] <= t <= s[2]]
        if not covering:
            return None
        return min(covering, key=lambda s: s[2] - s[1])[0]


def attach(ctx, source) -> Layers:
    """Give `ctx` the layers `source`: a `Layers`, or the path of the
    trace file to read them from."""
    layers = source if isinstance(source, Layers) else Layers.read(source)
    _found[ctx] = layers
    return layers


def of(ctx) -> Layers | None:
    """The layers of `ctx`'s trace, from the harness's trace directory;
    None where no trace file there holds the context's ops."""
    if ctx not in _found:
        _found[ctx] = None
        pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**",
                               "*.xplane.pb")
        paths = glob.glob(pattern, recursive=True) if ctx.ops else []
        for path in sorted(paths, key=os.path.getmtime, reverse=True):
            layers = Layers.read(path)
            if layers.holds(ctx.ops):
                _found[ctx] = layers
                break
    return _found[ctx]


def scope_share_pct(ctx, scope: str) -> float | None:
    """Percent of the device's busy time in the ops of `scope`; None when
    no op of the trace is in it."""
    layers = of(ctx)
    if layers is None:
        return None

    def pred(op):
        return layers.scope(op) == scope
    return ctx.busy_share_pct(pred) if ctx.select(pred) else None


def host_ms_per_solve(ctx) -> float | None:
    """Mean over the traced solves of each solve's span less the time in
    its `repro.wait` spans, in ms: the host's own time per solve."""
    layers = of(ctx)
    waits = [(a, b) for name, a, b in (layers.spans if layers else [])
             if name == WAIT]
    if not waits or not ctx.spans:
        return None
    host = [(e - s) - sum(min(b, e) - max(a, s) for a, b in waits
                          if a < e and b > s)
            for s, e in ctx.spans]
    return sum(host) / len(host) / 1e6


def idle_gaps(ctx) -> list[tuple[str, float]]:
    """As `Context.idle_gaps`, with a gap inside a solve named by the
    program span that covers its midpoint (`solve: repro.assemble`), and
    by where it falls where none does."""
    layers = of(ctx)
    if not ctx.spans:
        return []
    _, merged = trace.union_ns((o.start, o.end) for o in ctx.ops)
    cuts = sorted({t for span in ctx.spans for t in span})
    edges = [(cuts[0], cuts[0])] + [tuple(iv) for iv in merged] + \
        [(cuts[-1], cuts[-1])]
    totals: dict[str, float] = {}
    for (_, a), (b, _) in zip(edges, edges[1:]):
        inner = [t for t in cuts if a < t < b]
        for lo, hi in zip([a] + inner, inner + [b]):
            if hi > lo:
                mid = 0.5 * (lo + hi)
                name = ctx._name_gap(mid)
                span = layers.span_at(mid) if layers else None
                if span and name.startswith("solve:"):
                    name = f"solve: {span}"
                totals[name] = totals.get(name, 0.0) + (hi - lo) / 1e9
    return sorted(totals.items(), key=lambda g: -g[1])


def breakdown(ctx, top: int = trace.TOP) -> dict:
    """As `Context.breakdown`, each op's name led by its scope
    (`dda.subgrad %fusion.12 fusion f32[7680,4096]`) and the idle gaps
    named by `idle_gaps`."""
    layers = of(ctx)
    by_name: dict[str, float] = {}
    for o in ctx.ops:
        kind = f"{o.opcode}:{o.target}" if o.target else o.opcode
        key = f"{o.name} {kind} {o.shape}"
        scope = layers.scope(o) if layers else ""
        key = f"{scope} {key}" if scope else key
        by_name[key] = by_name.get(key, 0.0) + o.self_ns
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps(ctx)[:top]]}


def coverage(ctx) -> dict | None:
    """How much the program's names cover: percent of the device's busy
    time in scoped ops and in ops the compiler made (`compiler_made`);
    percent of the busy time of the ops the program traced inside the
    loops in scoped ops; percent of the idle time inside solves in gaps
    named by a program span; each layer's share; the unscoped ops by
    self time."""
    layers = of(ctx)
    if layers is None or not ctx.busy_ns:
        return None
    own = [o for o in ctx.ops
           if o.loops and not compiler_made(layers.stack(o))]
    own_ns = sum(o.self_ns for o in own)
    gaps = [(n, s) for n, s in idle_gaps(ctx) if n.startswith("solve:")]
    idle = sum(s for _, s in gaps)
    named = sum(s for n, s in gaps if n.startswith("solve: " + SPAN))
    shares: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    for o in ctx.ops:
        scope = layers.scope(o)
        shares[scope] = shares.get(scope, 0.0) + o.self_ns
        if not scope:
            key = f"{o.name} {o.opcode} {o.shape}"
            unscoped[key] = unscoped.get(key, 0.0) + o.self_ns
    return {
        "scoped_busy_pct": ctx.busy_share_pct(
            lambda o: bool(layers.scope(o))),
        "compiler_made_busy_pct": ctx.busy_share_pct(
            lambda o: compiler_made(layers.stack(o))),
        "scoped_own_loop_pct": 100.0 * sum(
            o.self_ns for o in own if layers.scope(o)) / own_ns
        if own_ns else None,
        "named_idle_pct": 100.0 * named / idle if idle else None,
        "scope_pct": {k or "(none)": 100.0 * v / ctx.busy_ns for k, v in
                      sorted(shares.items(), key=lambda kv: -kv[1])},
        "unscoped_ops_s": [[k, v / 1e9] for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:trace.TOP]]}
