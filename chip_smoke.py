"""Smoke test on TPU chips: the system's main paths, run once and checked.

    python chip_smoke.py               # one chip: dense DDA + experiment server
    python chip_smoke.py --four-chips  # four chips: consensus LM mesh only

One chip runs two phases:

  * dense -- `repro.run()` of the paper's non-smooth problem (section V.B)
    at n=256 nodes, M=30, d=4096 on a 4-regular expander, three times:
    communicate every iteration, every 4th (the mix under `lax.cond`), and
    top-k compressed gossip (the compress-mix kernel). Each compiled scan
    program must hold the Pallas kernel (`tpu_custom_call`), and a second
    run of the same program must give the same result. (`bench/run.py`
    holds every DDA cell to its float32 reference, `bench/dda_ref.py`.)
  * serve -- an in-process `ExperimentServer` answering `Client` requests
    over TCP: a cold request, a warm repeat (a compile-cache hit, identical
    to a solo `repro.run()`), and a packed pair compared with its solo runs.

Four chips run only the launch phase: `repro.run()` on the launch backend
with one LM replica per chip on the `pod` mesh axis, then one gossip round
checked against float64 numpy. The model is the llama3-8b smoke variant:
the phase proves the mesh and the collectives, not a model width.

Each phase prints its own lines. The last line of standard output is one
JSON object, printed only when every phase passed:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
The script exits non-zero when JAX finds no TPU (there is no CPU fallback)
and when any phase fails. Everything runs in this one process: a chip
belongs to one process at a time.

The phase functions take their sizes as arguments and adapt to the
platform, so they can be rehearsed at a tiny size on the CPU; `main`
refuses the CPU.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: relative tolerance on F between a packed lane and its solo run. Both are
#: float32 and differ only in the order of each round's sums, which grows
#: at worst linearly in the rounds: T * eps_f32 = 300 * 1.19e-7 = 3.6e-5 at
#: T=300; the tolerance leaves ~3x above that.
DENSE_RTOL = 1e-4

#: the gossip check compares bfloat16 parameters, rounded twice on the way
#: (the local step's output, the mixed result: 2^-9 relative each), as a
#: max error over the leaf's max; the tolerance leaves 2x above that
GOSSIP_RTOL = 2.0 ** -7


class SmokeFailure(AssertionError):
    """A phase's result is wrong."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def _peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _canon(result) -> str:
    """The result as the serving tier compares it: everything but the
    wall-clock measurements and serve bookkeeping, as canonical JSON."""
    from repro.serve import comparable_result_dict
    return json.dumps(comparable_result_dict(result), sort_keys=True)


def nonsmooth_spec(name: str, *, n: int, M: int, d: int, T: int,
                   eval_every: int, schedule: dict, compression=None,
                   r: float = 0.01):
    from repro.experiments import ExperimentSpec
    return ExperimentSpec(
        name=name,
        problem={"kind": "nonsmooth",
                 "params": {"n": n, "M": M, "d": d, "seed": 0}},
        topology={"kind": "expander", "params": {"k": 4, "seed": 0}},
        schedule=schedule,
        backends=[{"kind": "dense"}],
        # the stepsize of the repo's fig2_sparse manifest (same problem)
        stepsize={"kind": "sqrt", "params": {"A": 0.004}},
        compression=compression, T=T, eval_every=eval_every, seed=0, r=r)


def _check_trace(name: str, result, T: int, eval_every: int) -> None:
    fv = result.trace.fvals
    _check(len(fv) == T // eval_every, f"{name}: {len(fv)} trace points")
    _check(all(math.isfinite(v) for v in fv), f"{name}: non-finite F")


def _kernel_programs(spec) -> tuple:
    """Run `spec` through the serving layer's compile cache, which keeps
    the simulator, and return (result, programs holding the Pallas kernel,
    programs compiled)."""
    from repro.serve import CompileCache, execute_requests

    def _not_cached():
        raise SmokeFailure("the simulator was not kept in the cache")

    cache = CompileCache()
    (result,), _ = execute_requests([spec], [None], cache)
    with cache.lease(spec, spec.backends[0], _not_cached) as (sim, _):
        texts = [exe.as_text() for exe in sim._compiled.values()]
    return result, sum("tpu_custom_call" in t for t in texts), len(texts)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

DENSE_CASES = (
    ("every", {"kind": "every"}, None),
    ("periodic_h4", {"kind": "periodic", "params": {"h": 4}}, None),
    ("topk_0.125", {"kind": "every"},
     {"kind": "topk", "params": {"keep": 0.125}}),
)


def dense_phase(n: int = 256, M: int = 30, d: int = 4096, T: int = 300,
                eval_every: int = 25) -> dict:
    import repro

    tpu = _on_tpu()
    out = {}
    for name, schedule, compression in DENSE_CASES:
        shape = dict(n=n, M=M, d=d, T=T, eval_every=eval_every,
                     schedule=schedule, compression=compression)
        spec = nonsmooth_spec(f"dense_{name}", **shape)
        res = repro.run(spec)
        peak = _peak_bytes()
        _check_trace(name, res, T, eval_every)
        _check(res.extras["mix_mode"] == "sparse",
               f"{name}: mix_mode {res.extras['mix_mode']}")
        again, n_kernel, n_prog = _kernel_programs(spec)
        _check(n_prog >= 1, f"{name}: no compiled scan program")
        if tpu:
            _check(n_kernel == n_prog, f"{name}: {n_prog - n_kernel} of "
                   f"{n_prog} programs lack the Pallas kernel")
        _check(_canon(again) == _canon(res),
               f"{name}: a second run of the same program differs")
        row = {"compile_s": res.metrics.compile_s,
               "execute_s": res.metrics.execute_s,
               "wait_s": res.metrics.phases["wait"]["total_s"],
               "final_F": res.trace.fvals[-1],
               "peak_bytes_in_use": peak,
               "kernel_programs": f"{n_kernel}/{n_prog}"}
        print(f"[dense] {name}: " + " ".join(f"{k}={v!r}"
                                             for k, v in row.items()),
              flush=True)
        out[name] = row
    return out


def serve_phase(n: int = 256, M: int = 30, d: int = 4096, T: int = 300,
                eval_every: int = 25) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    import repro
    from repro.serve import Client, ExperimentServer

    shape = dict(n=n, M=M, d=d, T=T, eval_every=eval_every)
    base = nonsmooth_spec("serve_every", schedule={"kind": "every"}, **shape)
    pair = [nonsmooth_spec("serve_h4", r=0.01, **shape,
                           schedule={"kind": "periodic", "params": {"h": 4}}),
            nonsmooth_spec("serve_h2", r=0.05, **shape,
                           schedule={"kind": "periodic", "params": {"h": 2}})]
    out: dict = {}
    # a lane flushes at two requests; a lone request waits max_wait_s
    with ExperimentServer(processes=0, max_width=2, max_wait_s=2.0) as srv:
        host, port = srv.start()
        with Client(host, port) as client:
            t0 = time.perf_counter()
            cold = client.run(base)
            out["cold_request_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            warm = client.run(base)
            out["warm_request_s"] = time.perf_counter() - t0
        c_cold, c_warm = cold.metrics.counters, warm.metrics.counters
        _check(c_cold.get("cache_miss") == 1, f"cold counters {c_cold}")
        _check(c_warm.get("cache_hit") == 1, f"warm counters {c_warm}")
        out["cold_compile_s"] = cold.metrics.compile_s
        out["warm_compile_s"] = warm.metrics.compile_s
        solo = repro.run(base)
        _check_trace("serve", warm, T, eval_every)
        _check(_canon(warm) == _canon(solo),
               "the warm served result differs from the solo repro.run()")
        out["warm_equals_solo"] = True

        def request(spec):
            with Client(host, port) as c:
                return c.run(spec)

        with ThreadPoolExecutor(2) as ex:
            served = list(ex.map(request, pair))
        for r in served:
            _check(r.metrics.counters.get("lane_width") == 2,
                   f"{r.spec.name}: lane_width "
                   f"{r.metrics.counters.get('lane_width')}")
    solos = [repro.run(s) for s in pair]
    for r, s in zip(served, solos):
        _check_trace(r.spec.name, r, T, eval_every)
        _check(r.trace.iters == s.trace.iters
               and r.trace.comms == s.trace.comms
               and r.trace.sim_time == s.trace.sim_time,
               f"{r.spec.name}: packed time axes differ from solo")
    out["packed_exact"] = all(_canon(r) == _canon(s)
                              for r, s in zip(served, solos))
    out["packed_rel_diff"] = max(_rel(r.trace.fvals, s.trace.fvals)
                                 for r, s in zip(served, solos))
    out["peak_bytes_in_use"] = _peak_bytes()
    print("[serve] " + " ".join(f"{k}={v!r}" for k, v in out.items()),
          flush=True)
    _check(out["packed_rel_diff"] <= DENSE_RTOL,
           f"packed lanes differ from solo by {out['packed_rel_diff']!r}")
    return out


def launch_phase(n_pods: int = 4, T: int = 4) -> dict:
    """Consensus LM training with one replica per device on the `pod` axis,
    then one gossip round checked on the host: the fused step's parameters
    must equal P (float64 numpy) applied to the local step's output."""
    import jax
    import numpy as np

    import repro
    from repro.data.pipeline import TokenStream
    from repro.experiments import ExperimentSpec
    from repro.experiments.components import build_component, topologies
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_consensus_steps
    from repro.launch.train import init_pod_state
    from repro.models import registry
    from repro.optim import adamw, cosine_lr
    from repro.runtime import sharding as shrules

    arch, variant, batch, seq = "llama3-8b", "smoke", 2, 64
    spec = ExperimentSpec(
        name="launch_smoke",
        problem={"kind": "lm", "params": {"arch": arch, "variant": variant,
                                          "batch_per_node": batch,
                                          "seq_len": seq}},
        topology={"kind": "ring"},
        schedule={"kind": "periodic", "params": {"h": 2}},
        backends=[{"kind": "launch", "params": {"mesh": [n_pods, 1, 1]}}],
        T=T, eval_every=1, seed=0, r=0.05)
    res = repro.run(spec)
    losses = res.trace.fvals
    _check(len(losses) == T and all(math.isfinite(v) for v in losses),
           f"launch losses {losses}")
    # periodic h=2 communicates at t = 3, 5, ...: H_T = (T - 1) // 2
    _check(res.extras["comm_rounds"] == (T - 1) // 2,
           f"comm_rounds {res.extras['comm_rounds']}")
    out: dict = {"losses": losses,
                 "step_walls": res.extras["step_walls"],
                 "step_comm": res.extras["step_comm"]}

    mesh = make_mesh((n_pods, 1, 1), ("pod", "data", "model"))
    graph = build_component(topologies, "ring", {}, n=n_pods)
    cfg = registry.get_config(arch, variant)
    optimizer = adamw(cosine_lr(3e-4, T))
    local, _, fused = make_consensus_steps(cfg, optimizer, graph, mesh)
    streams = [TokenStream(cfg.vocab_size, seq, batch, node_index=i,
                           num_nodes=n_pods, seed=0) for i in range(n_pods)]
    nexts = [next(s) for s in streams]
    for s in streams:
        s.close()
    batch_arr = {k: np.stack([b[k] for b in nexts])
                 for k in ("tokens", "labels")}
    with shrules.use_rules(shrules.DEFAULT_RULES, mesh):
        params, state, psh, ssh = init_pod_state(cfg, optimizer, mesh,
                                                 n_pods, seed=0)
        leaf = jax.tree.leaves(params)[0]
        placement = {sh.index[0].start: sh.device
                     for sh in leaf.addressable_shards}
        shard = dict(in_shardings=(psh, ssh, None),
                     out_shardings=(psh, ssh, None))
        p_local = jax.jit(local, **shard)(params, state, batch_arr)[0]
        p_fused = jax.jit(fused, **shard)(params, state, batch_arr)[0]
    out["pod_devices"] = {p: f"id={dev.id} coords={getattr(dev, 'coords', None)}"
                          for p, dev in sorted(placement.items())}
    _check(sorted(placement) == list(range(n_pods))
           and len({dev.id for dev in placement.values()}) == n_pods,
           f"pod replicas are not on {n_pods} distinct devices: "
           f"{out['pod_devices']}")
    P = graph.mixing_matrix()
    worst = unmixed = 0.0
    for a, b in zip(jax.tree.leaves(p_local), jax.tree.leaves(p_fused)):
        a = np.asarray(a, np.float64)
        want = np.einsum("pq,q...->p...", P, a)
        scale = max(float(np.max(np.abs(want))), 1e-30)
        err = np.abs(np.asarray(b, np.float64) - want)
        worst = max(worst, float(np.max(err)) / scale)
        # what the check would read had no gossip happened
        unmixed = max(unmixed, float(np.max(np.abs(a - want))) / scale)
    out["gossip_rel_err"] = worst
    out["unmixed_rel_err"] = unmixed
    print("[launch] " + " ".join(f"{k}={v!r}" for k, v in out.items()),
          flush=True)
    _check(worst <= GOSSIP_RTOL, f"fused gossip differs from P @ local by "
           f"{worst!r} (tolerance {GOSSIP_RTOL!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip consensus-mesh phase")
    args = ap.parse_args(argv)
    dev = device_info()
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("[device] no TPU found: this smoke test runs on the chip only",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"[device] {need} chips needed, found {dev['count']}",
              file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"[cache] compile cache: {enable_compile_cache()}", flush=True)
    phases = ((("launch", launch_phase),) if args.four_chips
              else (("dense", dense_phase), ("serve", serve_phase)))
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
