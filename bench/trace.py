"""Reduction of a `--trace 1` run's profiler trace to the numbers that
the per-layer readers (`bench/metrics/*.py`) take.

The profiler writes one `.xplane.pb`. On a TPU its device planes
(`/device:TPU:<i>`) hold a line of operations (`XLA Ops`), each named by
its HLO instruction's text; a loop (`while`) is itself an operation that
spans the operations of its body. The host plane holds the harness's own
spans (`bench.solve`, one around each solve), on the same clock.

The trace carries no JAX name stack, so layers are found by the
program's structure (`bench/scopes.py`): the dense simulator runs each
solve as one program whose scan over iterations is one `while`, run
once per evaluation segment, with the evaluation outside it.

Busy time is the union of the operation intervals on a chip, averaged
over the chips the cell uses; the traced window runs from the first
solve's start to the last solve's end. An operation's self time is its
duration less that of the operations nested in it, so that shares add
up to the busy time.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from typing import Callable, Iterable

#: the device planes' line of operations
OPS_LINE = "XLA Ops"
#: the harness's span around one solve
SOLVE_SPAN = "bench.solve"
#: how many entries each list of the breakdown keeps
TOP = 10

_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_OPCODE = re.compile(r"([a-z][a-z0-9\-]*)\(")


@functools.lru_cache(maxsize=None)
def parse_instruction(text: str) -> tuple[str, str, str, str]:
    """(name, shape, opcode, custom-call target) of an HLO instruction's
    text, e.g. `%fusion.12 = f32[7680,4096]{1,0} fusion(...)`."""
    name, _, rest = text.partition(" = ")
    i = 0
    if rest.startswith("("):  # a tuple shape: skip its parentheses
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        i += 1
    m = _OPCODE.search(rest, i)
    opcode = m.group(1) if m else ""
    shape = "(...)" if rest.startswith("(") else \
        (rest[:m.start()] if m else rest).split("{")[0].strip()
    t = _TARGET.search(rest)
    return name.strip(), shape, opcode, t.group(1) if t else ""


@dataclasses.dataclass
class Op:
    name: str        # HLO instruction name, e.g. `%fusion.12`
    shape: str       # its result's shape, without layout
    opcode: str      # e.g. `fusion`, `while`, `sort`, `custom-call`
    target: str      # a custom call's target, else ""
    start: float     # ns, on the trace's clock
    end: float
    self_ns: float = 0.0
    #: the `while` ops this one runs inside, outermost first
    loops: tuple = ()


def make_op(text: str, start: float, end: float) -> Op:
    return Op(*parse_instruction(text), start, end)


def _nest(ops: list[Op]) -> list[Op]:
    """Self times and enclosing loops, from how the ops' intervals nest."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        op.loops = tuple(s.name for s in stack if s.opcode == "while")
        stack.append(op)
    return ops


def union_ns(intervals: Iterable[tuple[float, float]]) -> tuple[float, list]:
    """(total length, merged intervals) of a set of intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def read_xplane(path: str, chips: int):
    """(ops per chip, solve spans) of one trace file, as recorded."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append((plane.name, [
                        make_op(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                        for e in line.events]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name == SOLVE_SPAN]
    devices.sort(key=lambda d: d[0])
    return [ops for _, ops in devices[:chips]], sorted(spans)


class Context:
    """What a per-layer reader reads: the traced window's device
    operations and busy time, where the scan over iterations is, the
    cell, the window's counts and the chip's peaks."""

    def __init__(self, chip_ops: list[list[Op]], spans: list, cell, window,
                 device: dict):
        self.cell = cell
        self.window = window
        self.device = device
        self.spans = spans
        lo = spans[0][0] if spans else 0.0
        hi = spans[-1][1] if spans else 0.0
        self.window_ns = max(hi - lo, 0.0)
        #: every chip's ops inside the window, nested
        self.chip_ops = [_nest([o for o in ops
                                if o.start >= lo and o.end <= hi])
                         for ops in chip_ops]
        busy = [union_ns((o.start, o.end) for o in ops)
                for ops in self.chip_ops]
        self._merged = busy[0][1] if busy else []
        self.busy_ns = (sum(b for b, _ in busy) / len(busy)) if busy else 0.0
        self.iteration_loop = self._find_iteration_loop()

    def _find_iteration_loop(self) -> str | None:
        """The `while` that scans over iterations. It runs once per
        evaluation segment, T / eval_every times in each traced solve,
        and is the innermost loop that does; the loops of a problem's own
        (`eigh`'s) run many times per segment, and a loop over segments
        once per solve."""
        traffic = self.cell.traffic
        if not self.spans or not traffic.get("eval_every"):
            return None
        want = traffic["T"] // traffic["eval_every"] * len(self.spans)
        counts: dict[str, int] = {}
        for o in self.ops:
            if o.opcode == "while":
                counts[o.name] = counts.get(o.name, 0) + 1
        found = {n for n, c in counts.items() if c == want}
        outer = {loop for o in self.ops if o.name in found
                 for loop in o.loops}
        inner = sorted(found - outer)
        return inner[0] if len(inner) == 1 else None

    @property
    def problem_module(self):
        """The configuration's `bench/problems/<kind>.py`."""
        from bench import harness
        return harness.load_module(self.cell.root, "problems",
                                   self.cell.cfg["problem"]["kind"])

    @property
    def peak(self) -> dict:
        """The chip's peaks; a device kind with no table is an error."""
        from bench import roofline
        return roofline.peaks(self.device["kind"], self.cell.root)

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def ops(self) -> list[Op]:
        """The first chip's ops (the cells of one chip have only these)."""
        return self.chip_ops[0] if self.chip_ops else []

    @property
    def iterations(self) -> int:
        """Iterations the traced solves ran."""
        return self.window.iterations

    def in_iteration(self, op: Op) -> bool:
        """Inside the scan over iterations, the loop op itself included."""
        return self.iteration_loop is not None and (
            op.name == self.iteration_loop or self.iteration_loop in op.loops)

    def select(self, pred: Callable[[Op], bool]) -> list[Op]:
        return [o for o in self.ops if pred(o)]

    def self_ns(self, pred: Callable[[Op], bool]) -> float:
        return sum(o.self_ns for o in self.ops if pred(o))

    def busy_share_pct(self, pred: Callable[[Op], bool]) -> float | None:
        """Percent of the device's busy time in the ops `pred` selects,
        or None when the trace holds no device op."""
        if not self.busy_ns:
            return None
        return 100.0 * self.self_ns(pred) / self.busy_ns

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Seconds the device sat idle inside the window, totalled by
        what the harness was doing then, most first: inside a solve
        before its first device op (the solve's host work up to the
        dispatch), after its last (result assembly), between two of its
        device ops, in a solve with no device op at all, or between
        two solves."""
        if not self.spans:
            return []
        cuts = sorted({t for span in self.spans for t in span})
        edges = [(cuts[0], cuts[0])] + \
            [tuple(iv) for iv in self._merged] + [(cuts[-1], cuts[-1])]
        totals: dict[str, float] = {}
        for (_, a), (b, _) in zip(edges, edges[1:]):
            # cut each idle gap where a solve starts or ends
            inner = [t for t in cuts if a < t < b]
            for lo, hi in zip([a] + inner, inner + [b]):
                if hi > lo:
                    name = self._name_gap(0.5 * (lo + hi))
                    totals[name] = totals.get(name, 0.0) + (hi - lo) / 1e9
        return sorted(totals.items(), key=lambda g: -g[1])

    def _name_gap(self, mid: float) -> str:
        for s, e in self.spans:
            if s <= mid <= e:
                inside = [iv for iv in self._merged
                          if s <= iv[0] <= e or s <= iv[1] <= e]
                if not inside:
                    return "solve: no device op in the solve"
                if mid < inside[0][0]:
                    return "solve: host before its first device op"
                if mid > inside[-1][1]:
                    return "solve: host after its last device op"
                return "solve: host between device ops"
        return "harness: between solves"

    def breakdown(self) -> dict:
        """The device ops that took most self time, by HLO instruction,
        and the idle time by what the harness was doing."""
        by_name: dict[str, float] = {}
        for o in self.ops:
            kind = f"{o.opcode}:{o.target}" if o.target else o.opcode
            key = f"{o.name} {kind} {o.shape}"
            by_name[key] = by_name.get(key, 0.0) + o.self_ns
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:TOP]]}


def context(trace_dir: str, cell, window, device: dict) -> Context:
    """The `Context` of the trace that `trace_dir` holds."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    chip_ops, spans = ([], [])
    if files:
        chip_ops, spans = read_xplane(max(files, key=os.path.getmtime),
                                      cell.chips)
    return Context(chip_ops, spans, cell, window, device)
