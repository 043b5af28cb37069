"""The chip's peaks and the least time a piece of work can take on it.

Peaks live in `bench/peaks/<device kind>.json`, one file per kind, with
their source; a kind with no file is an error, never a default. Work is
counted by the functions that `bench/problems/<kind>.py` keeps, from
shapes: what the algorithm needs, whatever implements it.
"""

from __future__ import annotations

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    path = root / "bench" / "peaks" / f"{device_kind.replace(' ', '_')}.json"
    if not path.is_file():
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(looked for {path.name})")
    with open(path) as f:
        table = json.load(f)
    if table["device_kind"] != device_kind:
        raise KeyError(f"{path.name} is for {table['device_kind']!r}")
    return table


def least_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(flops / peak["flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])
