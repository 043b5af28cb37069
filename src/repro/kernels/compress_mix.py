"""Fused compress-mix: masked consensus accumulation in one memory pass.

Compressed gossip on a k-regular graph mixes SPARSIFIED neighbor messages
against the node's own exact state (the diagonal never compresses itself):

    out[i] = w_self[i] * z[i] + sum_j w_edge[i, j] * (msg_nbr * mask_nbr)

Materializing the masked message first (`sent = corrected * mask`, then
the plain weighted mix) reads the (k, n, M) neighbor tiles twice and
writes an (n, M) intermediate. This kernel fuses the mask multiply into
the same VMEM-resident accumulation pass `gossip_mix_weighted` uses --
the compress step rides along for free on an op that is purely
bandwidth-bound, which is exactly the regime where top-k/rand-k messages
would otherwise have forced the dense O(n^2 d) matmul split.

Layout mirrors `gossip_mix.gossip_mix_weighted`: (8, 1024) data tiles
over (nodes, dims), the k neighbor message AND mask stacks as leading-dim
operands with the small degree loop unrolled in-kernel, and the per-node
weight columns as (8, 1) blocks broadcasting across the lane dimension.
The caller (`ops.compress_mix_impl`) gathers/pads; the mask is 0/1 in the
message dtype so the multiply stays in the fp32 accumulation type.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gossip_mix import _LANES, _SUBLANES


def _compress_mix_kernel(self_ref, nbr_ref, mask_ref, wself_ref, wedge_ref,
                         out_ref, *, k: int):
    """One (nodes, dims) tile: acc = w_self⊙self
    + sum_j w_edge[:, j]⊙(msg_j⊙mask_j)."""
    acc = wself_ref[...] * self_ref[...].astype(jnp.float32)
    for j in range(k):  # k is small (graph degree); unrolled
        acc += wedge_ref[j] * (nbr_ref[j].astype(jnp.float32)
                               * mask_ref[j].astype(jnp.float32))
    out_ref[...] = acc.astype(out_ref.dtype)


def compress_mix_weighted(self_buf: jax.Array, neighbor_msgs: jax.Array,
                          neighbor_masks: jax.Array, w_self: jax.Array,
                          w_edge: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """Stacked-node masked mix with per-edge weight vectors.

    self_buf: (n, M) exact own states; neighbor_msgs: (k, n, M) slot j
    holding the corrected message node i receives from its j-th
    in-neighbor (already gathered); neighbor_masks: (k, n, M) the matching
    0/1 supports; w_self: (n,); w_edge: (n, k). n must be a multiple of 8
    and M of 1024 (the caller pads; see ops.compress_mix_impl).
    """
    n, M = self_buf.shape
    k = neighbor_msgs.shape[0]
    assert n % _SUBLANES == 0, n
    assert M % _LANES == 0, M
    assert neighbor_masks.shape == neighbor_msgs.shape
    ws = w_self.astype(jnp.float32).reshape(n, 1)
    we = w_edge.astype(jnp.float32).T.reshape(k, n, 1)
    grid = (n // _SUBLANES, M // _LANES)
    return pl.pallas_call(
        functools.partial(_compress_mix_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_SUBLANES, _LANES), lambda i, j: (i, j)),
            pl.BlockSpec((k, _SUBLANES, _LANES), lambda i, j: (0, i, j)),
            pl.BlockSpec((k, _SUBLANES, _LANES), lambda i, j: (0, i, j)),
            pl.BlockSpec((_SUBLANES, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((k, _SUBLANES, 1), lambda i, j: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, M), self_buf.dtype),
        interpret=interpret,
        name="compress_mix_weighted",
    )(self_buf, neighbor_msgs, neighbor_masks, ws, we)
