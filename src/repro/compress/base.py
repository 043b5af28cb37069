"""Compressor objects: the jax and numpy halves of one wire format.

Every compressor bundles THREE things, mirroring how `components.Problem`
carries both its numpy (netsim) and jax (dense) execution halves:

  * a jax-traceable stack API (`compress_jax(corrected, t)` on a stacked
    (n, d) array, `t` the traced iteration counter) used inside
    `DDASimulator`'s scanned body -- sparsifiers additionally expose
    `support_mask_jax` so the fused compress-mix Pallas pass can consume
    the 0/1 support directly instead of a materialized masked message;
  * a numpy per-message API (`compress_np(row, node, stamp)`) used by the
    event-driven netsim engines. Randomized compressors derive their RNG
    from `(seed, node, stamp)` -- a pure function of WHAT is being sent,
    never of global draw order -- which is what keeps the object and
    vectorized engines bit-identical under compression: each node's sends
    occur in increasing stamp order in both engines, so per-node residual
    sequences coincide exactly;
  * a per-message byte model (`wire_ratio(d)`): the fraction of the
    uncompressed d-float payload that actually crosses the wire (top-k:
    k values plus k indices over d values). This is the c in the
    paper's effective tradeoff r -> r*c (n_opt = 1/sqrt(rc), h_opt ~
    sqrt(nkrc)); `netsim.Network` scales its serialization times by it and
    `core.tradeoff` accepts it as the `c=` argument everywhere.

All compressors return the DENSE representation of the transmitted
message (zeros off the support for sparsifiers, dequantized values for
quantizers) so downstream mixing code sees one layout; bytes-on-wire are
accounted through `wire_ratio`, never through array sizes.

Error feedback (`error_feedback=True`, the default for every lossy
compressor) is owned by the CALLER -- the compressor is a pure function
of the corrected message `m + residual`; the caller keeps
`residual <- corrected - sent`. The telescoping identity
`sum sent = sum msg + res_0 - res_T` then makes the cumulative
transmitted mass unbiased (pinned by tests/test_compress.py).
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "VALUE_BYTES",
    "INDEX_BYTES",
    "Compressor",
    "NoCompression",
    "TopK",
    "RandK",
    "Int8",
    "keep_count",
    "topk_mask_jax",
    "topk_mask_np",
    "topk_indices_flat",
]

#: wire width of one transmitted float value / coordinate index
VALUE_BYTES = 4
INDEX_BYTES = 4


def keep_count(d: int, keep: float) -> int:
    """Entries kept per d-dim message at fraction `keep` (always >= 1)."""
    if not 0.0 < keep <= 1.0:
        raise ValueError(f"keep must be in (0, 1], got {keep}")
    return max(1, min(d, int(d * keep)))


# ---------------------------------------------------------------------------
# the one exact-top-k implementation (satellite: the dense simulator's old
# inline `mags >= thresh` mask kept MORE than k entries on magnitude ties;
# every top-k consumer now routes through these)
# ---------------------------------------------------------------------------


def topk_indices_flat(x: jax.Array, k: int) -> jax.Array:
    """Indices of the k largest-|x| entries of a flat vector; exactly k,
    ties broken toward the lower index (`lax.top_k` is stable)."""
    return jax.lax.top_k(jnp.abs(x.reshape(-1)), k)[1]


def topk_mask_jax(x: jax.Array, k: int) -> jax.Array:
    """Exactly-k per-row 0/1 support mask of the k largest-|x| entries.
    x: (n, d). A thresholding mask (`|x| >= kth largest`) is NOT
    equivalent: on magnitude ties it keeps every tied entry."""
    n = x.shape[0]
    idx = jax.lax.top_k(jnp.abs(x), k)[1]
    return jnp.zeros(x.shape, x.dtype).at[
        jnp.arange(n)[:, None], idx].set(1)


def topk_mask_np(row: np.ndarray, k: int) -> np.ndarray:
    """Numpy twin of `topk_mask_jax` for one (d,) message: stable argsort
    on -|x| breaks ties toward the lower index, matching `lax.top_k`."""
    idx = np.argsort(-np.abs(row), kind="stable")[:k]
    mask = np.zeros_like(row)
    mask[idx] = 1.0
    return mask


# ---------------------------------------------------------------------------
# compressors
# ---------------------------------------------------------------------------


class Compressor:
    """Interface; see the module docstring for the three halves."""

    kind: ClassVar[str] = "?"
    #: sparsifiers expose `support_mask_jax` and ride the fused
    #: compress-mix kernel; quantizers ship a dense dequantized message
    is_sparsifier: ClassVar[bool] = False
    error_feedback: bool = False

    def wire_ratio(self, d: int) -> float:
        """Bytes-on-wire fraction vs the uncompressed d-float message."""
        raise NotImplementedError

    def compress_jax(self, corrected: jax.Array, t: jax.Array) -> jax.Array:
        """Dense layout of what is transmitted, (n, d) -> (n, d)."""
        raise NotImplementedError

    def compress_np(self, row: np.ndarray, node: int,
                    stamp: int) -> np.ndarray:
        """One message, (d,) -> (d,); must return a fresh array."""
        raise NotImplementedError

    def params_dict(self) -> dict:
        """The spec params that rebuild this compressor (JSON-exact)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


@dataclasses.dataclass(frozen=True)
class NoCompression(Compressor):
    """Identity wire format: ratio 1, no residual ever accumulates."""

    kind: ClassVar[str] = "none"
    error_feedback: bool = False

    def wire_ratio(self, d: int) -> float:
        return 1.0

    def compress_jax(self, corrected, t):
        return corrected

    def compress_np(self, row, node, stamp):
        return row.copy()


@dataclasses.dataclass(frozen=True)
class TopK(Compressor):
    """Largest-|x| sparsification: keep `keep_count(d, keep)` coordinates,
    ship (value, index) pairs."""

    kind: ClassVar[str] = "topk"
    is_sparsifier: ClassVar[bool] = True
    keep: float = 0.1
    error_feedback: bool = True

    def __post_init__(self):
        keep_count(1, self.keep)  # validates the range eagerly

    def wire_ratio(self, d: int) -> float:
        k = keep_count(d, self.keep)
        return k * (VALUE_BYTES + INDEX_BYTES) / (d * VALUE_BYTES)

    def support_mask_jax(self, corrected, t):
        return topk_mask_jax(corrected, keep_count(corrected.shape[-1],
                                                   self.keep))

    def compress_jax(self, corrected, t):
        return corrected * self.support_mask_jax(corrected, t)

    def compress_np(self, row, node, stamp):
        return row * topk_mask_np(row, keep_count(row.shape[-1], self.keep))


@dataclasses.dataclass(frozen=True)
class RandK(Compressor):
    """Uniform-random sparsification. The support is a pure function of
    (seed, round) -- shared randomness the receiver can replay -- so only
    the k VALUES cross the wire (no index bytes), which is why rand-k's
    ratio beats top-k's at equal keep."""

    kind: ClassVar[str] = "randk"
    is_sparsifier: ClassVar[bool] = True
    keep: float = 0.1
    seed: int = 0
    error_feedback: bool = True

    def __post_init__(self):
        keep_count(1, self.keep)

    def wire_ratio(self, d: int) -> float:
        return keep_count(d, self.keep) / d

    def support_mask_jax(self, corrected, t):
        k = keep_count(corrected.shape[-1], self.keep)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 t.astype(jnp.int32))
        # exactly-k random support per node row: top-k of i.i.d. scores
        scores = jax.random.uniform(key, corrected.shape)
        idx = jax.lax.top_k(scores, k)[1]
        return jnp.zeros(corrected.shape, corrected.dtype).at[
            jnp.arange(corrected.shape[0])[:, None], idx].set(1)

    def compress_jax(self, corrected, t):
        return corrected * self.support_mask_jax(corrected, t)

    def compress_np(self, row, node, stamp):
        d = row.shape[-1]
        k = keep_count(d, self.keep)
        rng = np.random.default_rng((self.seed, int(node), int(stamp)))
        out = np.zeros_like(row)
        idx = rng.permutation(d)[:k]
        out[idx] = row[idx]
        return out


@dataclasses.dataclass(frozen=True)
class Int8(Compressor):
    """Per-message absmax int8 quantization: scale s = max|x|/127, ship
    int8 codes + one float scale. `stochastic=True` rounds with
    floor(x/s + u), u ~ U[0,1) -- unbiased per entry (E[q] = x/s) -- the
    pattern `pltpu.stochastic_round` implements in hardware."""

    kind: ClassVar[str] = "int8"
    stochastic: bool = False
    seed: int = 0
    error_feedback: bool = True

    #: quantization levels on each side of zero
    LEVELS: ClassVar[int] = 127

    def wire_ratio(self, d: int) -> float:
        return (d * 1 + VALUE_BYTES) / (d * VALUE_BYTES)

    def _dequant(self, y, q, s):
        return jnp.clip(q, -self.LEVELS, self.LEVELS) * s

    def compress_jax(self, corrected, t):
        s = jnp.max(jnp.abs(corrected), axis=-1, keepdims=True) / self.LEVELS
        s = jnp.where(s > 0, s, 1.0)
        y = corrected / s
        if self.stochastic:
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                     t.astype(jnp.int32))
            q = jnp.floor(y + jax.random.uniform(key, y.shape))
        else:
            q = jnp.round(y)
        return self._dequant(y, q, s).astype(corrected.dtype)

    def compress_np(self, row, node, stamp):
        s = float(np.max(np.abs(row))) / self.LEVELS
        if s <= 0.0:
            return row.copy()
        y = row / s
        if self.stochastic:
            rng = np.random.default_rng((self.seed, int(node), int(stamp)))
            q = np.floor(y + rng.random(y.shape))
        else:
            q = np.round(y)
        return np.clip(q, -self.LEVELS, self.LEVELS) * s
