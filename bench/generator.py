"""The one traffic generator: a configuration, a traffic mix and a seed
make the solve requests of a run.

A traffic file under `bench/traffic/` holds only parameters: the loop
(`closed`: each client sends its next solve when the last one returns),
the number of clients, and each solve's schedule, T, evaluation cadence
and compression. The configuration supplies the problem, graph, stepsize
and r, and its problem kind what the seed draws (`seeded_problem` for
the DDA's problems: their data). Every seed gives the same shapes and
the same amount of work.

On the `launch` backend a solve is a consensus training run of T steps;
the seed goes only into the spec's own `seed`, which draws the weights
and the token streams.
"""

from __future__ import annotations

#: the run RNG seed of a spec is folded into a 32-bit key by the program
SEED_MODULUS = 2 ** 31


def seeded_problem(cfg: dict, seed: int) -> dict:
    """The configuration's problem with `seed` drawing its data."""
    problem = dict(cfg["problem"])
    problem["params"] = {**problem["params"], "seed": int(seed)}
    return problem


def solve_request(cfg: dict, traffic: dict, seed: int, index: int,
                  spec_problem=seeded_problem) -> dict:
    """Keyword arguments of the `index`-th solve's `ExperimentSpec`;
    `spec_problem(cfg, seed)` is the problem kind's spec problem
    (`bench/check.py`'s `hooks`). A configuration without a `stepsize`
    leaves the spec's default, which the launch backend requires."""
    request = dict(name=f"{traffic['name']}.{index}",
                   problem=spec_problem(cfg, seed),
                   topology=cfg["topology"], schedule=traffic["schedule"],
                   backends=[cfg["backend"]],
                   compression=traffic["compression"], T=traffic["T"],
                   eval_every=traffic["eval_every"],
                   seed=int(seed) % SEED_MODULUS, r=cfg["r"])
    if "stepsize" in cfg:
        request["stepsize"] = cfg["stepsize"]
    return request


def check_supported(cfg: dict, traffic: dict) -> None:
    """Refuse a mix this generator does not drive, or that the launch
    backend would not run as its reference (`bench/train_ref.py`) does."""
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise ValueError(f"traffic {traffic['name']!r}: only a closed loop "
                         f"with one client is driven")
    if traffic["T"] % traffic["eval_every"]:
        raise ValueError(f"traffic {traffic['name']!r}: T must be a "
                         f"multiple of eval_every")
    if cfg["backend"]["kind"] != "launch":
        return
    from bench import train_ref
    if traffic.get("compression") is not None:
        # the launch backend takes no compressor and would drop it unseen
        raise ValueError(f"traffic {traffic['name']!r}: the launch backend "
                         f"does not compress its gossip")
    if traffic["schedule"]["kind"] not in train_ref.SCHEDULES:
        raise ValueError(f"traffic {traffic['name']!r}: the training "
                         f"reference models the schedules "
                         f"{train_ref.SCHEDULES} only")
