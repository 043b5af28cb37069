"""Subgradient of the paper's non-smooth quadratics in one pass over the
centres (section V.B: f_i(x) = sum_j max(||x - c_ij0||^2, ||x - c_ij1||^2)).

    g_i = 2 * sum_j (x_i - c_ij[pick]),   pick = 1 iff q_ij1 > q_ij0,
    q_ijp = ||x_i - c_ijp||^2

The jnp body reads the (n, M, 2, d) centres once for the squared
distances, gathers the chosen ones and relays them out before it sums:
three reads of an array far larger than VMEM. Here each grid step DMAs a
block of centres into VMEM once and does all of it there: both distances,
the choice of piece, and the running sum.

Layout: the kernel reads its own copy of the centres, `(M, 2, n8, d)`
(`kernel_layout`), so that for a fixed pair j and piece p the rows of a
block of nodes are contiguous in HBM and sit on the sublanes, one node a
row, with d on the lanes. n8 is n rounded up to whole sublane tiles; the
padding nodes' centres are zeros, read with the rest, and their rows of
the output are dropped. A block of `block_nodes` nodes (a multiple of 8)
and `block_pairs` pairs is one DMA of `block_pairs * 2` runs of
`block_nodes * d` floats; the grid runs over node blocks, then over pair
blocks, which accumulate into the node block's output while it stays in
VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8
_LANES = 128
#: the centres' bytes a grid step reads: 16 nodes by 5 pairs at d=4096.
#: On a v5e chip every block from 8 to 32 nodes and 1 to 4 MiB read the
#: benchmark's 252 MB of centres at 689-703 GB/s.
_BLOCK_BYTES = 5 << 19


def kernel_layout(centers: jax.Array) -> jax.Array:
    """The kernel's copy of the (n, M, 2, d) centres: (M, 2, n8, d), n8
    the multiple of 8 at or above n, the padding nodes' centres zero."""
    pad = (-centers.shape[0]) % _SUBLANES
    ck = jnp.transpose(centers, (1, 2, 0, 3))
    return jnp.pad(ck, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else ck


def fits(d: int) -> bool:
    """Whether the kernel takes a width d: whole 128-lane tiles."""
    return d % _LANES == 0


def block_shape(n: int, M: int, d: int,
                block_bytes: int = _BLOCK_BYTES) -> tuple[int, int]:
    """(block_nodes, block_pairs): up to 16 nodes, a multiple of 8 that
    divides n, and the most pairs dividing M whose centres fit
    `block_bytes`."""
    nodes = 16 if n % 16 == 0 else _SUBLANES
    per_pair = 2 * nodes * d * 4
    pairs = max([p for p in range(1, M + 1)
                 if M % p == 0 and p * per_pair <= block_bytes] or [1])
    return nodes, pairs


def _kernel(x_ref, c_ref, out_ref, *, pairs: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]
    acc = out_ref[...]
    for j in range(pairs):  # pairs of one block; unrolled
        d0 = x - c_ref[j, 0]
        d1 = x - c_ref[j, 1]
        q0 = jnp.sum(d0 * d0, axis=-1, keepdims=True)
        q1 = jnp.sum(d1 * d1, axis=-1, keepdims=True)
        # the argmax rule: piece 1 only when strictly farther
        acc = acc + jnp.where(q1 > q0, d1, d0)
    out_ref[...] = acc

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = 2.0 * out_ref[...]


def nonsmooth_subgrad(x: jax.Array, centers_k: jax.Array, *,
                      block: tuple[int, int] | None = None,
                      interpret: bool = False) -> jax.Array:
    """x: (n, d) float32 node states; centers_k: (M, 2, n8, d), the
    centres in `kernel_layout`. Returns the (n, d) stacked subgradient.
    `block` is (block_nodes, block_pairs), `block_shape`'s by default."""
    M, _, n8, d = centers_k.shape
    n = x.shape[0]
    assert x.shape == (n, d) and n8 == n + (-n) % _SUBLANES and fits(d), \
        (x.shape, centers_k.shape)
    nodes, pairs = block or block_shape(n8, M, d)
    assert n8 % nodes == 0 and M % pairs == 0, (nodes, pairs)
    x = x.astype(jnp.float32)
    if n8 > n:
        x = jnp.pad(x, ((0, n8 - n), (0, 0)))
    c_block = pairs * 2 * nodes * d * 4
    # double-buffered centres, x and the output, and the body's temporaries
    vmem = 2 * c_block + 12 * nodes * d * 4 + (4 << 20)
    out = pl.pallas_call(
        functools.partial(_kernel, pairs=pairs),
        grid=(n8 // nodes, M // pairs),
        in_specs=[
            pl.BlockSpec((nodes, d), lambda i, j: (i, 0)),
            pl.BlockSpec((pairs, 2, nodes, d), lambda i, j: (j, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((nodes, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n8, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, 32 << 20)),
        interpret=interpret,
        name="nonsmooth_subgrad",
    )(x, centers_k)
    return out[:n] if n8 > n else out
