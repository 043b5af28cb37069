"""The layers that the program's language models name in the trace: the
named scopes of its blocks and training steps (`lm.*`).

- `lm.mla` (latent attention), `lm.attn` (grouped-query attention),
  `lm.mlp` (a dense FFN), `lm.moe` holding `lm.moe.route` (norm, router,
  top-k, balance loss), `lm.moe.experts` (dispatch, the held experts'
  grouped matmuls, combine) and `lm.moe.shared` (the shared experts),
  `lm.optimizer` (the AdamW update), `lm.gossip` (the fused step's mix of
  the replicas' parameters).
- An op belongs to the innermost `lm.*` scope of its name stack. The
  backward pass names its ops under the forward's scopes wrapped in the
  transform (`transpose(jvp(lm.moe))/...`), and rematerialised ops under
  the same scopes, so a scope's ops are its forward, recomputation and
  backward alike.

The name stacks come from `bench/layers.py`'s reading of the trace file;
where the trace holds none of them, as from a program that sets no `lm.*`
scope, the functions here return None.
"""

from __future__ import annotations

import re

from bench import layers

_SCOPE = re.compile(r"lm\.[A-Za-z0-9_.]*[A-Za-z0-9_]")


def scope_of(name_stack: str) -> str:
    """The innermost `lm.*` scope of a name stack, else ""."""
    found = _SCOPE.findall(name_stack)
    return found[-1] if found else ""


def within(scope: str, name: str) -> bool:
    """`name` is `scope` or a scope nested in it."""
    return name == scope or name.startswith(scope + ".")


def share_pct(ctx, scope: str) -> float | None:
    """Percent of the device's busy time in the ops of `scope` and the
    scopes nested in it; None when no op of the trace is in it."""
    found = layers.of(ctx)
    if found is None:
        return None

    def pred(op):
        return within(scope, scope_of(found.stack(op)))
    return ctx.busy_share_pct(pred) if ctx.select(pred) else None


def self_s(ctx, scope: str) -> float | None:
    """Seconds of self time in the ops of `scope` (nested ones too) on
    the first chip; None where none is."""
    found = layers.of(ctx)
    if found is None:
        return None
    ns = ctx.self_ns(lambda op: within(scope, scope_of(found.stack(op))))
    return ns / 1e9 if ns else None


def step_flops(ctx, part: str | None = None) -> float | None:
    """Model FLOPs of one replica's training step from the cell's problem
    module (`step_flops`), or None where it counts none."""
    module = ctx.problem_module
    if not hasattr(module, "step_flops"):
        return None
    return module.step_flops(ctx.cell.cfg, part)
