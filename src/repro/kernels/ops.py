"""Jit'd public wrappers for the Pallas kernels.

The platform picks the path: on a TPU the kernels compile to Mosaic
(`interpret=False`); elsewhere -- the CPU test path -- the gossip ops and
the non-smooth subgradient run their jnp references and the other kernels
run in the Pallas interpreter. An explicit `interpret=` / `use_kernel=`
argument overrides the choice (the tests use it to check kernel bodies in
interpret mode). `ref.py` holds the pure-jnp oracles used by the property
tests. Beside the subgradient's dispatch sits the same problem's
objective, `nonsmooth_objective`, one matmul with no kernel of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import nonsmooth_subgrad as _nsg
from repro.kernels import ref
from repro.kernels.compress_mix import compress_mix_weighted as _compress_w
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gossip_mix import (_LANES, _SUBLANES, _TILE,
                                      gossip_mix as _gossip,
                                      gossip_mix_weighted as _gossip_w)
from repro.kernels.selective_scan import selective_scan as _sscan
from repro.kernels.ssd_scan import ssd_scan as _ssd


def _on_tpu() -> bool:
    """True when computations run on a TPU, where the kernels compile."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    interpret: bool | None = None):
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _flash(q, k, v, causal=causal, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(x, dt, A, B, C, D_skip, *, interpret: bool | None = None):
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _sscan(x, dt, A, B, C, D_skip, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan(x, dt, A, B, C, *, interpret: bool | None = None):
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _ssd(x, dt, A, B, C, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("self_weight", "edge_weight",
                                    "interpret"))
def gossip_mix(self_buf, neighbor_bufs, self_weight: float,
               edge_weight: float, *, interpret: bool | None = None):
    """Pads the flat buffers to a whole tile count, mixes, and un-pads."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    (M,) = self_buf.shape
    pad = (-M) % _TILE
    sb = jnp.pad(self_buf, (0, pad))
    nb = jnp.pad(neighbor_bufs, ((0, 0), (0, pad)))
    out = _gossip(sb, nb, self_weight, edge_weight, interpret=interpret)
    return out[:M]


def gossip_gather_mix_impl(z, S_in, w_self, w_edge, *, msg=None,
                           interpret: bool | None = None,
                           use_kernel: bool | None = None):
    """Sparse consensus round on a stacked z: neighbor-index gather + the
    fused weighted accumulation (`gossip_mix_weighted`).

    z: (n, ...) stacked node states; S_in: (n, k) in-neighbor indices
    (S_in[i, j] = the node whose value node i receives in slot j);
    w_self: (n,); w_edge: (n, k). Equals `W @ z.reshape(n, -1)` for the
    mixing matrix W with diag(W) = w_self and W[i, S_in[i, j]] summing
    w_edge[i, j] over slots. `msg` (same shape as z) substitutes the
    TRANSMITTED stack for the neighbor gathers -- quantized gossip ships
    the dequantized `msg` while the diagonal keeps each node's exact own
    z -- and defaults to z itself (uncompressed).

    Dispatch: on a TPU the gather feeds the compiled Pallas kernel, which
    makes the k+1 AXPYs one VMEM-resident pass. Elsewhere (the CPU test
    path) the Pallas interpreter costs ~ms per grid cell -- two orders off
    the fused XLA lowering -- so the default routes to the jnp reference,
    which XLA fuses into a single gather+FMA loop. Tests pass
    `use_kernel=True` with `interpret=True` to validate the kernel body
    itself.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    use_kernel = (not interpret) if use_kernel is None else use_kernel
    if not use_kernel:
        return ref.gossip_gather_mix_ref(z, S_in, w_self, w_edge, msg=msg)
    n, k = S_in.shape
    # the kernel consumes weight VECTORS; scalar (uniform) weights are just
    # constant columns
    if jnp.ndim(w_self) == 0:
        w_self = jnp.full((n,), w_self, jnp.float32)
    if jnp.ndim(w_edge) == 0:
        w_edge = jnp.full((n, k), w_edge, jnp.float32)
    zf = z.reshape(n, -1)
    mf = zf if msg is None else msg.reshape(n, -1)
    M = zf.shape[1]
    pad_n = (-n) % _SUBLANES
    pad_m = (-M) % _LANES
    sb = jnp.pad(zf, ((0, pad_n), (0, pad_m)))
    nbr = jnp.pad(jnp.moveaxis(mf[S_in], 1, 0),
                  ((0, 0), (0, pad_n), (0, pad_m)))
    ws = jnp.pad(w_self, (0, pad_n))
    we = jnp.pad(w_edge, ((0, pad_n), (0, 0)))
    out = _gossip_w(sb, nbr, ws, we, interpret=interpret)
    return out[:n, :M].astype(z.dtype).reshape(z.shape)


def compress_mix_impl(z, msg, mask, S_in, w_self, w_edge, *,
                      interpret: bool | None = None,
                      use_kernel: bool | None = None):
    """Fused sparsified consensus round: gather each in-neighbor's
    corrected message AND its 0/1 transmitted support, then accumulate
    `w_self[i] z[i] + sum_j w_edge[i, j] (msg ⊙ mask)[S_in[i, j]]` in one
    VMEM-resident pass (`compress_mix.compress_mix_weighted`) -- the
    sparsify multiply rides the bandwidth-bound mix for free, which is
    what lets top-k/rand-k gossip stay on the O(nkd) sparse path instead
    of forcing the dense matmul split.

    Shapes and the ref/kernel dispatch contract match
    `gossip_gather_mix_impl`; `mask` is 0/1 in z's dtype.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    use_kernel = (not interpret) if use_kernel is None else use_kernel
    if not use_kernel:
        return ref.compress_mix_ref(z, msg, mask, S_in, w_self, w_edge)
    n, k = S_in.shape
    if jnp.ndim(w_self) == 0:
        w_self = jnp.full((n,), w_self, jnp.float32)
    if jnp.ndim(w_edge) == 0:
        w_edge = jnp.full((n, k), w_edge, jnp.float32)
    zf = z.reshape(n, -1)
    mf = msg.reshape(n, -1)
    kf = mask.reshape(n, -1)
    M = zf.shape[1]
    pad_n = (-n) % _SUBLANES
    pad_m = (-M) % _LANES
    sb = jnp.pad(zf, ((0, pad_n), (0, pad_m)))
    nbr = jnp.pad(jnp.moveaxis(mf[S_in], 1, 0),
                  ((0, 0), (0, pad_n), (0, pad_m)))
    msk = jnp.pad(jnp.moveaxis(kf[S_in], 1, 0),
                  ((0, 0), (0, pad_n), (0, pad_m)))
    ws = jnp.pad(w_self, (0, pad_n))
    we = jnp.pad(w_edge, ((0, pad_n), (0, 0)))
    out = _compress_w(sb, nbr, msk, ws, we, interpret=interpret)
    return out[:n, :M].astype(z.dtype).reshape(z.shape)


def nonsmooth_kernel_layout(centers):
    """The kernel's copy of the (n, M, 2, d) centres
    (`nonsmooth_subgrad.kernel_layout`) where `nonsmooth_subgrad_impl`
    calls the kernel -- on a TPU, for d a multiple of 128 -- else None.
    A problem makes it once, when it is built."""
    if not (_on_tpu() and _nsg.fits(centers.shape[-1])):
        return None
    return _nsg.kernel_layout(centers)


def nonsmooth_subgrad_impl(x_stack, centers, centers_k, *,
                           interpret: bool | None = None,
                           use_kernel: bool | None = None):
    """Stacked subgradient of the section V.B non-smooth quadratics:
    x_stack (n, d), centers (n, M, 2, d), centers_k their kernel layout
    or None (`nonsmooth_kernel_layout`).

    Dispatch: on a TPU, given the kernel's layout, the Pallas kernel
    `nonsmooth_subgrad` reads the centres once; elsewhere, and where the
    shapes do not fit the kernel, the jnp reference runs. Under `vmap`
    the centres stay unbatched: the kernel's grid gains the lane axis and
    reads them once per lane. Tests pass `use_kernel=True` with
    `interpret=True` to check the kernel body."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    if use_kernel is None:
        use_kernel = not interpret and centers_k is not None
    if not use_kernel:
        return ref.nonsmooth_subgrad_ref(x_stack, centers)
    return _nsg.nonsmooth_subgrad(x_stack, centers_k, interpret=interpret)


def nonsmooth_objective(x, centers_rows, centers_sq):
    """F(x) of the section V.B non-smooth quadratics, x (d,): the (n, M,
    2, d) centres given as their (2nM, d) matrix of rows, `centers_rows`,
    and their squared norms (n, M, 2), `centers_sq`, both made once with
    the problem. Each squared distance splits as ||x||^2 - 2<x, c> +
    ||c||^2, so F(x) = M ||x||^2 + mean_i sum_j max_k (||c_ijk||^2 -
    2<x, c_ijk>): the cross terms are one matvec against the rows, and
    under `vmap` over x one matmul on the MXU. The max keeps the farther
    centre of each pair, so the split cancels little; where F is far
    below M ||x||^2 it loses digits in that ratio. The product is taken at
    "highest": the TPU's default runs a float32 dot as one bfloat16 pass.
    `ref.nonsmooth_objective_ref` is the direct-difference form."""
    M = centers_sq.shape[1]
    cross = jnp.dot(centers_rows, x, precision=jax.lax.Precision.HIGHEST)
    far = jnp.max(centers_sq - 2.0 * cross.reshape(centers_sq.shape), axis=-1)
    return M * jnp.sum(x * x) + jnp.mean(jnp.sum(far, axis=-1))


#: jitted front doors; hot loops that are already inside their own jit call
#: the `_impl` functions directly so the mix inlines into the caller's
#: program (a nested pjit is a fusion boundary XLA will not cross)
gossip_gather_mix = functools.partial(
    jax.jit, static_argnames=("interpret", "use_kernel"))(
        gossip_gather_mix_impl)
compress_mix = functools.partial(
    jax.jit, static_argnames=("interpret", "use_kernel"))(
        compress_mix_impl)

__all__ = ["flash_attention", "selective_scan", "ssd_scan", "gossip_mix",
           "gossip_gather_mix", "gossip_gather_mix_impl",
           "compress_mix", "compress_mix_impl", "nonsmooth_kernel_layout",
           "nonsmooth_subgrad_impl", "nonsmooth_objective", "ref"]
