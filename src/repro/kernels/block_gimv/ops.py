"""Jit'd public wrapper for the dense-region GIM-V kernel (pad + dispatch)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_gimv.block_gimv import SEMIRINGS, dense_gimv_multi_pallas, dense_gimv_pallas

__all__ = ["dense_gimv", "dense_gimv_multi", "semiring_of", "has_semiring"]

_SEMIRING_TABLE = {
    ("mul", "sum"): "plus_times",
    ("add", "min"): "min_plus",
    ("add", "max"): "max_plus",
    ("src", "min"): "min_src",
}


def semiring_of(combine2: str, combine_all: str) -> str:
    """Map a GimvSpec's (combine2, combineAll) to a kernel semiring id."""
    key = (combine2, combine_all)
    if key not in _SEMIRING_TABLE:
        raise ValueError(f"no kernel semiring for {key}")
    return _SEMIRING_TABLE[key]


def has_semiring(combine2: str, combine_all: str) -> bool:
    """Whether the (combine2, combineAll) pair has a Pallas kernel semiring
    (the engine's backend='pallas' falls back to 'xla' when it does not)."""
    return (combine2, combine_all) in _SEMIRING_TABLE


def _pad_identity(semiring: str, dtype):
    """Padding value for the matrix such that padded columns are no-ops."""
    if semiring == "plus_times":
        return 0
    if semiring in ("min_plus",):
        return np.inf
    if semiring == "max_plus":
        return -np.inf
    return 0  # min_src: presence 0 -> masked inside the kernel


@partial(jax.jit, static_argnames=("semiring", "tile_m", "tile_k", "tile_q", "interpret"))
def dense_gimv_multi(
    m: jnp.ndarray,
    v: jnp.ndarray,
    *,
    semiring: str,
    tile_m: int = 128,
    tile_k: int = 128,
    tile_q: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query dense block GIM-V with automatic tile padding.

    m: [M, K], v: [K, Q] -> r: [M, Q].  plus_times defaults to a 128-wide
    query tile (full MXU); the tropical semirings, which reduce one query
    column at a time, take the whole query axis up to 128 queries.
    """
    assert semiring in SEMIRINGS
    if tile_q is None:
        tile_q = 128 if semiring == "plus_times" or v.shape[1] > 128 else v.shape[1]
    M, K = m.shape
    _, Q = v.shape
    Mp = -(-M // tile_m) * tile_m
    Kp = -(-K // tile_k) * tile_k
    Qp = -(-Q // tile_q) * tile_q
    if (Mp, Kp) != (M, K):
        pad_val = _pad_identity(semiring, m.dtype)
        m = jnp.pad(m, ((0, Mp - M), (0, Kp - K)), constant_values=pad_val)
    if (Kp, Qp) != (K, Q):
        # Padded K rows are never selected (matrix padding is the identity);
        # padded Q columns are sliced off below.
        v = jnp.pad(v, ((0, Kp - K), (0, Qp - Q)))
    out = dense_gimv_multi_pallas(
        m, v, semiring=semiring, out_dtype=v.dtype,
        tile_m=tile_m, tile_k=tile_k, tile_q=tile_q, interpret=interpret,
    )
    return out[:M, :Q]


@partial(jax.jit, static_argnames=("semiring", "tile_m", "tile_k", "interpret"))
def dense_gimv(
    m: jnp.ndarray,
    v: jnp.ndarray,
    *,
    semiring: str,
    tile_m: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Dense block GIM-V with automatic tile padding.  m: [M, K], v: [K]."""
    assert semiring in SEMIRINGS
    M, K = m.shape
    Mp = -(-M // tile_m) * tile_m
    Kp = -(-K // tile_k) * tile_k
    if (Mp, Kp) != (M, K):
        pad_val = _pad_identity(semiring, m.dtype)
        m = jnp.pad(m, ((0, Mp - M), (0, Kp - K)), constant_values=pad_val)
        # Padded v entries are never selected: matrix padding is the identity.
        v = jnp.pad(v, (0, Kp - K))
    out = dense_gimv_pallas(
        m, v, semiring=semiring, out_dtype=v.dtype,
        tile_m=tile_m, tile_k=tile_k, interpret=interpret,
    )
    return out[:M]
