"""Pure-jnp oracles for every Pallas kernel and for
`ops.nonsmooth_objective` (the allclose targets in tests/test_kernels.py).
Deliberately naive and readable."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        sm_scale: float | None = None) -> jax.Array:
    """q: (B,H,Sq,D); k,v: (B,KH,Sk,D). Plain softmax attention in fp32."""
    B, H, Sq, D = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    group = H // KH
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def selective_scan_ref(x, dt, A, B, C, D_skip) -> jax.Array:
    """Mamba-1 recurrence, sequential over tokens.
    x, dt: (Bt, S, d); A: (d, N); B, C: (Bt, S, N); D_skip: (d,).
    Returns y: (Bt, S, d) fp32."""
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    Bm = B.astype(jnp.float32)
    Cm = C.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        dA = jnp.exp(dt_t[..., None] * A)            # (Bt, d, N)
        dBx = (dt_t * x_t)[..., None] * B_t[:, None, :]
        h = dA * h + dBx
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    Bt, S, d = x.shape
    h0 = jnp.zeros((Bt, d, A.shape[1]), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    _, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)
    return y + x * D_skip


def ssd_scan_ref(x, dt, A, B, C) -> jax.Array:
    """Mamba-2 SSD recurrence, sequential oracle.
    x: (Bt,S,H,P); dt: (Bt,S,H); A: (H,) negative; B, C: (Bt,S,N).
    Returns y: (Bt,S,H,P) fp32 (no D skip, no gating)."""
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    Bm = B.astype(jnp.float32)
    Cm = C.astype(jnp.float32)
    Bt, S, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp                    # (Bt,H,P),(Bt,H),(Bt,N)
        dA = jnp.exp(dt_t * A)                       # (Bt,H)
        dBx = jnp.einsum("bhp,bn->bhpn", x_t * dt_t[..., None], B_t)
        h = dA[..., None, None] * h + dBx
        y = jnp.einsum("bhpn,bn->bhp", h, C_t)
        return h, y

    h0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1)


def gossip_mix_ref(self_buf, neighbor_bufs, self_weight, edge_weight
                   ) -> jax.Array:
    """out = sw * self + ew * sum_k neighbor_k.
    self_buf: (M,); neighbor_bufs: (K, M)."""
    acc = self_weight * self_buf.astype(jnp.float32)
    acc = acc + edge_weight * jnp.sum(neighbor_bufs.astype(jnp.float32), 0)
    return acc.astype(self_buf.dtype)


def gossip_mix_weighted_ref(self_buf, neighbor_bufs, w_self, w_edge
                            ) -> jax.Array:
    """out[i] = w_self[i] * self[i] + sum_j w_edge[i, j] * nbr[j, i].
    self_buf: (n, M); neighbor_bufs: (K, n, M); w_self: (n,);
    w_edge: (n, K)."""
    acc = w_self[:, None] * self_buf.astype(jnp.float32)
    acc = acc + jnp.einsum("nk,knm->nm", w_edge.astype(jnp.float32),
                           neighbor_bufs.astype(jnp.float32))
    return acc.astype(self_buf.dtype)


def gossip_gather_mix_ref(z, S_in, w_self, w_edge, msg=None) -> jax.Array:
    """One sparse consensus round on a stacked z, as a gather + weighted sum:
    out[i] = w_self[i] z[i] + sum_j w_edge[i, j] src[S_in[i, j]].
    z: (n, ...); S_in: (n, K) in-neighbor indices; w_self: (n,) or scalar;
    w_edge: (n, K) or scalar (uniform lazy weights: one multiply over the
    summed gathers instead of K weight broadcasts). `msg` (same shape as
    z) substitutes the TRANSMITTED stack for the neighbor gathers --
    compressed gossip ships `msg` while the diagonal keeps the node's
    exact own z -- and defaults to z itself (uncompressed)."""
    n, k = S_in.shape
    zf = z.reshape(n, -1).astype(jnp.float32)
    mf = zf if msg is None else msg.reshape(n, -1).astype(jnp.float32)
    if jnp.ndim(w_edge) == 0:
        acc = mf[S_in[:, 0]]
        for j in range(1, k):
            acc = acc + mf[S_in[:, j]]
        out = w_self * zf + w_edge * acc
        return out.astype(z.dtype).reshape(z.shape)
    acc = w_self[:, None] * zf
    for j in range(k):
        acc = acc + w_edge[:, j][:, None] * mf[S_in[:, j]]
    return acc.astype(z.dtype).reshape(z.shape)


def compress_mix_ref(z, msg, mask, S_in, w_self, w_edge) -> jax.Array:
    """Masked (sparsified) consensus round:
    out[i] = w_self[i] z[i]
             + sum_j w_edge[i, j] (msg ⊙ mask)[S_in[i, j]].
    z/msg/mask: (n, ...) with mask the 0/1 transmitted support; S_in:
    (n, K); weights as in `gossip_gather_mix_ref`. The allclose target for
    `compress_mix.compress_mix_weighted`."""
    n = S_in.shape[0]
    sent = (msg.reshape(n, -1).astype(jnp.float32)
            * mask.reshape(n, -1).astype(jnp.float32))
    return gossip_gather_mix_ref(z, S_in, w_self, w_edge,
                                 msg=sent.reshape(z.shape))


def nonsmooth_subgrad_ref(x_stack, centers) -> jax.Array:
    """Stacked subgradient of the section V.B non-smooth quadratics,
    g_i = 2 sum_j (x_i - c_ij[pick]) with pick the argmax of the two
    squared distances (ties to piece 0). x_stack: (n, d); centers:
    (n, M, 2, d). The allclose target for
    `nonsmooth_subgrad.nonsmooth_subgrad`."""
    diff = x_stack[:, None, None, :] - centers          # (n, M, 2, d)
    q = jnp.sum(diff * diff, axis=-1)                   # (n, M, 2)
    pick = jnp.argmax(q, axis=-1)                       # (n, M)
    chosen = jnp.take_along_axis(
        diff, pick[..., None, None], axis=2)[:, :, 0]   # (n, M, d)
    return 2.0 * jnp.sum(chosen, axis=1)


def nonsmooth_objective_ref(x, centers) -> jax.Array:
    """F(x) of the section V.B non-smooth quadratics, the mean over nodes
    of sum_j max(||x - c_ij0||^2, ||x - c_ij1||^2), by direct differences.
    x: (d,); centers: (n, M, 2, d). The allclose target for
    `ops.nonsmooth_objective`."""
    diff = x[None, None, None, :] - centers
    q = jnp.sum(diff * diff, axis=-1)
    return jnp.mean(jnp.sum(jnp.max(q, axis=-1), axis=-1))
