"""Fused consensus mixing: out = w_self * own + w_edge * sum_k neighbor_k.

The consensus hot loop (paper eq. 3: z <- sum_j p_ij z_j) applied to a
k-regular graph materializes k received buffers; mixing them with k separate
AXPY passes reads the output k+1 times. This kernel fuses the weighted
accumulation into ONE pass over memory -- the op is purely bandwidth-bound,
so the fusion is worth ~(k+1)x on HBM traffic for the mixing step.

Two kernels:

  * `gossip_mix` -- one node's flat buffer against k received buffers with
    scalar weights (the shard_map per-node layout). Blocks are (8, 1024)
    tiles over the flattened parameter buffer (the caller pads/reshapes);
    neighbors are stacked on a leading dim and the small k loop is unrolled
    inside the kernel (all operands for one tile resident in VMEM).
  * `gossip_mix_weighted` -- the STACKED (n, d) layout of the dense
    simulator with per-edge WEIGHT VECTORS: w_self is (n,) and w_edge is
    (n, k), so a reweighted mixing matrix (`AdaptiveController
    (reweight_gossip=True)`'s `Network.mix_weights`) runs through the same
    fused pass as the uniform lazy weights (which are just constant
    vectors). Blocks tile (nodes, dims); the per-node weight columns ride
    along as (rows, 1) blocks that broadcast across the lane dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 1024
_SUBLANES = 8
_TILE = _LANES * _SUBLANES


def _mix_kernel(self_ref, nbr_ref, out_ref, *, k: int, self_weight: float,
                edge_weight: float):
    acc = self_weight * self_ref[...].astype(jnp.float32)
    for j in range(k):  # k is small (graph degree); unrolled
        acc += edge_weight * nbr_ref[j].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def gossip_mix(self_buf: jax.Array, neighbor_bufs: jax.Array,
               self_weight: float, edge_weight: float, *,
               interpret: bool = False) -> jax.Array:
    """self_buf: (M,) flat parameters; neighbor_bufs: (k, M) received
    buffers. M is padded to a whole number of (8,1024) tiles by the caller
    (see ops.gossip_mix_padded)."""
    (M,) = self_buf.shape
    k = neighbor_bufs.shape[0]
    assert M % _TILE == 0, M
    rows = M // _LANES
    s2 = self_buf.reshape(rows, _LANES)
    n2 = neighbor_bufs.reshape(k, rows, _LANES)
    grid = (rows // _SUBLANES,)
    out = pl.pallas_call(
        functools.partial(_mix_kernel, k=k, self_weight=self_weight,
                          edge_weight=edge_weight),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((k, _SUBLANES, _LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), self_buf.dtype),
        interpret=interpret,
    )(s2, n2)
    return out.reshape(M)


def _mix_kernel_weighted(self_ref, nbr_ref, wself_ref, wedge_ref, out_ref,
                         *, k: int):
    """One (nodes, dims) tile: acc = w_self⊙self + sum_j w_edge[:,j]⊙nbr_j.

    The weight blocks are (SUBLANES, 1) columns that broadcast across the
    lane dimension -- one extra scalar per node row, so the pass stays
    bandwidth-bound on the data tiles exactly like the uniform kernel.
    """
    acc = wself_ref[...] * self_ref[...].astype(jnp.float32)
    for j in range(k):  # k is small (graph degree); unrolled
        acc += wedge_ref[j] * nbr_ref[j].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def gossip_mix_weighted(self_buf: jax.Array, neighbor_bufs: jax.Array,
                        w_self: jax.Array, w_edge: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """Stacked-node mix with per-edge weight vectors.

    self_buf: (n, M) -- node-major rows of flattened parameters;
    neighbor_bufs: (k, n, M) -- slot j holds the buffer node i receives
    from its j-th in-neighbor (already gathered by the caller);
    w_self: (n,) diagonal weights; w_edge: (n, k) per-(node, slot) weights.
    n must be a multiple of 8 and M a multiple of 1024 (the caller pads;
    see ops.gossip_gather_mix).
    """
    n, M = self_buf.shape
    k = neighbor_bufs.shape[0]
    assert n % _SUBLANES == 0, n
    assert M % _LANES == 0, M
    ws = w_self.astype(jnp.float32).reshape(n, 1)
    we = w_edge.astype(jnp.float32).T.reshape(k, n, 1)
    grid = (n // _SUBLANES, M // _LANES)
    return pl.pallas_call(
        functools.partial(_mix_kernel_weighted, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_SUBLANES, _LANES), lambda i, j: (i, j)),
            pl.BlockSpec((k, _SUBLANES, _LANES), lambda i, j: (0, i, j)),
            pl.BlockSpec((_SUBLANES, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((k, _SUBLANES, 1), lambda i, j: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, M), self_buf.dtype),
        interpret=interpret,
        name="gossip_mix_weighted",
    )(self_buf, neighbor_bufs, ws, we)
