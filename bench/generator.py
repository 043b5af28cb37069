"""The one traffic generator: a configuration, a traffic mix and a seed
make the solve requests of a run.

A traffic file under `bench/traffic/` holds only parameters: the loop
(`closed`: each client sends its next solve when the last one returns),
the number of clients, and each solve's schedule, T, evaluation cadence
and compression. The configuration supplies the problem, graph, stepsize
and r. The seed draws the problem's data, so every seed gives the same
shapes and the same amount of work.
"""

from __future__ import annotations

#: the run RNG seed of a spec is folded into a 32-bit key by the program
SEED_MODULUS = 2 ** 31


def solve_request(cfg: dict, traffic: dict, seed: int, index: int) -> dict:
    """Keyword arguments of the `index`-th solve's `ExperimentSpec`."""
    problem = dict(cfg["problem"])
    problem["params"] = {**problem["params"], "seed": int(seed)}
    return dict(name=f"{traffic['name']}.{index}", problem=problem,
                topology=cfg["topology"], schedule=traffic["schedule"],
                backends=[cfg["backend"]], stepsize=cfg["stepsize"],
                compression=traffic["compression"], T=traffic["T"],
                eval_every=traffic["eval_every"],
                seed=int(seed) % SEED_MODULUS, r=cfg["r"])


def check_supported(traffic: dict) -> None:
    """Refuse a mix this generator does not drive."""
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise ValueError(f"traffic {traffic['name']!r}: only a closed loop "
                         f"with one client is driven")
    if traffic["T"] % traffic["eval_every"]:
        raise ValueError(f"traffic {traffic['name']!r}: T must be a "
                         f"multiple of eval_every")
