"""Compile cache: warm `DDASimulator` instances keyed by program shape,
and, for the launch backend, warm `ConsensusProgram`s (its mesh, jitted
init and step programs and their executables) under the same signature.

The cost structure the server amortizes is XLA compilation: a cold
`repro.run()` on the dense backend traces + lowers + compiles the scanned
program (seconds) and then executes it (milliseconds). Every compiled
executable lives in `DDASimulator._compiled`, keyed by argument
shapes/dtypes -- so holding the *simulator* across requests is holding the
compile cache. `CompileCache` does exactly that: one simulator per
**cache signature**, leased to one run at a time.

The signature is the dense scan program's shape identity -- everything
that changes what gets compiled or the constants baked into it:

  * the problem component verbatim (kind AND params: n, d and the data
    seed -- problem arrays are closure constants in the XLA program);
  * the topology component verbatim (k, graph seed -- the mixing matrix
    is a baked constant);
  * the stepsize component verbatim (a(t) closure constants);
  * T and eval_every (scan lengths / segment shapes);
  * the schedule component's KIND only -- its params (h, p) are the comm
    MASK, which is *data* to the scanned program, not shape. The kind
    stays in the key per the issue's contract; note "every" vs "periodic"
    also picks the cond-free all-comm program variant;
  * the resolved backend component (its params shape a launch program;
    the dense backend takes none);
  * controller presence/params (an adaptive run drives the per-segment
    program; a plain run drives the whole-run scan).

Deliberately NOT in the key -- the per-request knobs a warm simulator is
rebound with before each run: `seed` (PRNG fold, data), `r` (host-side
time-axis bookkeeping), `eps_frac`/`name` (host-side bookkeeping).

Thread-safety: a global lock guards the table; each entry has its own
RLock held for the duration of a lease, so two requests with the same
signature serialize on the simulator (its run methods mutate
`last_timings`) while different signatures run concurrently. Eviction is
LRU over non-leased entries only.
"""

from __future__ import annotations

import contextlib
import json
import threading
from collections import OrderedDict
from typing import Any, Callable, Iterator

from repro.experiments.spec import ComponentSpec, ExperimentSpec

__all__ = ["CompileCache", "cache_signature"]

#: spec fields that never shape the compiled program (rebound per lease)
CACHE_FREE_FIELDS = ("name", "seed", "r", "eps_frac")


def cache_signature(spec: ExperimentSpec,
                    backend: ComponentSpec | None = None) -> str:
    """Canonical JSON string identifying the compiled-program family a
    dense spec runs on; see the module docstring for what is in and out.
    Two specs with equal signatures can safely share one warm
    `DDASimulator` (per-request knobs rebound under the lease)."""
    d = spec.to_dict()
    d.pop("spec_version", None)
    for f in CACHE_FREE_FIELDS:
        d.pop(f, None)
    d.pop("backends", None)  # the RESOLVED backend is keyed instead
    d["schedule"] = d["schedule"]["kind"]  # params are mask data
    b = backend.to_dict() if backend is not None else None
    return json.dumps([d, b], sort_keys=True)


class _Entry:
    __slots__ = ("sim", "lock", "active", "hits")

    def __init__(self):
        self.sim: Any = None
        self.lock = threading.RLock()
        self.active = 0  # leases currently held (never evict while > 0)
        self.hits = 0


class CompileCache:
    """LRU table of warm simulators, one per cache signature.

    `lease(spec, backend, factory)` is the whole API: a context manager
    yielding `(sim, hit)`. On a miss `factory()` builds the simulator
    (under the entry lock, so concurrent first requests for one signature
    build once and the rest wait and hit). The caller must treat the
    simulator as exclusively theirs for the lease's duration and rebind
    any per-request knobs (`sim.schedule`, `sim.r`) before running.
    """

    def __init__(self, max_entries: int = 32):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: OrderedDict[str, _Entry] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @contextlib.contextmanager
    def lease(self, spec: ExperimentSpec, backend: ComponentSpec,
              factory: Callable[[], Any]) -> Iterator[tuple[Any, bool]]:
        sig = cache_signature(spec, backend)
        with self._lock:
            entry = self._entries.get(sig)
            hit = entry is not None
            if hit:
                self._entries.move_to_end(sig)
                self.hits += 1
                entry.hits += 1
            else:
                entry = _Entry()
                self._entries[sig] = entry
                self.misses += 1
            entry.active += 1
        try:
            with entry.lock:
                if entry.sim is None:
                    entry.sim = factory()
                yield entry.sim, hit
        finally:
            with self._lock:
                entry.active -= 1
                self._evict_locked()

    def _evict_locked(self) -> None:
        while len(self._entries) > self.max_entries:
            victim = next((sig for sig, e in self._entries.items()
                           if e.active == 0), None)
            if victim is None:  # every entry leased: nothing evictable now
                return
            del self._entries[victim]
            self.evictions += 1

    def stats(self) -> dict[str, Any]:
        with self._lock:
            total = self.hits + self.misses
            return {
                "entries": len(self._entries),
                "capacity": self.max_entries,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": (self.hits / total) if total else 0.0,
            }
