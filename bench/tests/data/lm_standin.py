"""A stand-in plain model for the benchmark's CPU tests of training cells.

The tests copy this file into a test root as `bench/problems/lm.py`. It
wraps the program's own transformer at the registry's small ("smoke")
configuration of the configuration's `arch`, so that the tests check the
training loop of `bench/train_ref.py` (replicas, tokens, AdamW, gossip)
on its own. A configuration that the benchmark measures brings a plain
model written out from its published description instead.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.train_ref import (NUMBERS, readings,  # noqa: F401
                             reference_trace, spec_problem)


def _model(cfg: dict, dtype=None):
    from repro.models import registry
    p = cfg["problem"]["params"]
    model = registry.get_config(p["arch"], p["variant"])
    return model if dtype is None else dataclasses.replace(model, dtype=dtype)


def init(key, cfg: dict):
    from repro.models import transformer
    return transformer.init(key, _model(cfg))[0]


def loss(params, tokens, labels, cfg: dict, dtype):
    from repro.models import transformer
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    return transformer.loss_fn(params, {"tokens": tokens, "labels": labels},
                               _model(cfg, jnp.dtype(dtype)))
