"""Jit'd public wrappers for the scatter-combine kernel (pad + dispatch)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.scatter_combine.scatter_combine import (
    SEMIRINGS,
    packed_scatter_combine_multi_pallas,
    packed_scatter_combine_pallas,
    scatter_combine_multi_pallas,
    scatter_combine_pallas,
)

__all__ = ["scatter_combine_gimv", "scatter_combine_gimv_multi",
           "packed_scatter_combine_gimv", "packed_scatter_combine_gimv_multi"]


@partial(jax.jit, static_argnames=("n_out", "semiring", "tile_n", "tile_t", "interpret"))
def scatter_combine_gimv(
    idx: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    semiring: str,
    tile_n: int = 128,
    tile_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Scatter-combine with automatic tile padding.  idx/val: [T] -> [n_out].

    Pad entries (idx < 0 or idx >= n_out) contribute the combineAll identity.
    """
    assert semiring in SEMIRINGS
    (T,) = idx.shape
    Tp = max(-(-T // tile_t) * tile_t, tile_t)
    Np = -(-n_out // tile_n) * tile_n
    if Tp != T:
        idx = jnp.pad(idx, (0, Tp - T), constant_values=-1)
        val = jnp.pad(val, (0, Tp - T))
    out = scatter_combine_pallas(
        idx.astype(jnp.int32), val, Np, semiring=semiring, out_dtype=val.dtype,
        tile_n=tile_n, tile_t=tile_t, interpret=interpret)
    return out[:n_out]


def _query_tile(Q: int) -> tuple[int, int]:
    """(tile_q, Qp): the whole query axis as one lane block up to 128
    queries (a block's minor dim must be the full dim or a multiple of 128),
    128-wide tiles beyond."""
    if Q <= 128:
        return Q, Q
    return 128, -(-Q // 128) * 128


def _packed_tile_t(width: int) -> int:
    """Slot tile of the packed kernels: 128 words of 32/width ids each, so
    the word block is one full 128-lane row."""
    return 128 * (32 // width)


@partial(jax.jit, static_argnames=("n_out", "semiring", "tile_n", "tile_t", "interpret"))
def scatter_combine_gimv_multi(
    idx: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    semiring: str,
    tile_n: int = 128,
    tile_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query scatter-combine with automatic tile padding.

    idx: [T], val: [T, Q] -> r [n_out, Q]."""
    assert semiring in SEMIRINGS
    T, Q = val.shape
    Tp = max(-(-T // tile_t) * tile_t, tile_t)
    Np = -(-n_out // tile_n) * tile_n
    tile_q, Qp = _query_tile(Q)
    if Tp != T:
        idx = jnp.pad(idx, (0, Tp - T), constant_values=-1)
        val = jnp.pad(val, ((0, Tp - T), (0, 0)))
    if Qp != Q:
        val = jnp.pad(val, ((0, 0), (0, Qp - Q)))
    out = scatter_combine_multi_pallas(
        idx.astype(jnp.int32), val, Np, semiring=semiring, out_dtype=val.dtype,
        tile_n=tile_n, tile_t=tile_t, tile_q=tile_q, interpret=interpret)
    return out[:n_out, :Q]


@partial(jax.jit, static_argnames=("n_out", "set_slots", "n_local", "width",
                                   "semiring", "tile_n", "interpret"))
def packed_scatter_combine_gimv(
    words: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    set_slots: int,
    n_local: int,
    width: int,
    semiring: str,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Indexed-payload scatter-combine with automatic tile padding.

    ``words`` bit-pack the scatter targets (codec.pack_uniform at ``width``
    bits, 32/width ids per uint32); ``val`` [T] is the payload in the same
    static order.  Slot t belongs to set t // set_slots and targets row
    decode(t) + set*(n_local+1); ids >= n_local land in the set's drop slot.
    Tile padding is safe by construction: padded slots resolve to sets past
    n_out and are sliced off.
    """
    assert semiring in SEMIRINGS
    (T,) = val.shape
    k = 32 // width
    tile_t = _packed_tile_t(width)
    Tp = max(-(-T // tile_t) * tile_t, tile_t)
    Np = -(-n_out // tile_n) * tile_n
    if Tp != T:
        words = jnp.pad(words, (0, (Tp - T) // k))
        val = jnp.pad(val, (0, Tp - T))
    out = packed_scatter_combine_pallas(
        words.astype(jnp.uint32), val, Np, set_slots=set_slots,
        n_local=n_local, width=width, semiring=semiring, out_dtype=val.dtype,
        tile_n=tile_n, tile_t=tile_t, interpret=interpret)
    return out[:n_out]


@partial(jax.jit, static_argnames=("n_out", "set_slots", "n_local", "width",
                                   "semiring", "tile_n", "interpret"))
def packed_scatter_combine_gimv_multi(
    words: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    set_slots: int,
    n_local: int,
    width: int,
    semiring: str,
    tile_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query indexed-payload scatter-combine with tile padding.

    words: [T*width/32] uint32, val: [T, Q] -> r [n_out, Q]."""
    assert semiring in SEMIRINGS
    T, Q = val.shape
    k = 32 // width
    tile_t = _packed_tile_t(width)
    Tp = max(-(-T // tile_t) * tile_t, tile_t)
    Np = -(-n_out // tile_n) * tile_n
    tile_q, Qp = _query_tile(Q)
    if Tp != T:
        words = jnp.pad(words, (0, (Tp - T) // k))
        val = jnp.pad(val, ((0, Tp - T), (0, 0)))
    if Qp != Q:
        val = jnp.pad(val, ((0, 0), (0, Qp - Q)))
    out = packed_scatter_combine_multi_pallas(
        words.astype(jnp.uint32), val, Np, set_slots=set_slots,
        n_local=n_local, width=width, semiring=semiring, out_dtype=val.dtype,
        tile_n=tile_n, tile_t=tile_t, tile_q=tile_q, interpret=interpret)
    return out[:n_out, :Q]
