"""Pallas TPU kernel: scatter-combine for the sparse-exchange receive side.

``sparse_exchange.scatter_partials`` folds the received compact partials
(idx, val) into the owner's result vector r[n_local] with the semiring's
combineAll.  The XLA lowering is a segment op — serial scatter traffic on
TPU.  This kernel recasts it as tiled one-hot reduction work:

    onehot[n, t] = (idx[t] == n)            over a (TN, TI) tile
    r[n]        = combineAll_t where(onehot[n, t], val[t], identity)

For plus_times the inner reduce IS a matmul (onehot @ val) and runs on the
MXU; the tropical semirings reduce on the VPU.  The output tile is revisited
along the idx-tile grid axis and accumulated in place — the same pattern as
the dense / ELL kernels.

Pad entries use idx = -1 (or any index outside the covered range): they
match no one-hot row and contribute the identity.  Compare-and-reduce work
is O(T * n_out / tile) — worth it when the serial scatter dominates (large
fan-in partials on real hardware); interpret mode is for parity tests only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_gimv.block_gimv import SEMIRINGS, _combine_all, _identity


def _scatter_combine_kernel(idx_ref, val_ref, o_ref, *, semiring: str, tile_n: int):
    t = pl.program_id(1)
    base = pl.program_id(0) * tile_n
    idx = idx_ref[...]                       # (1, TI) int32; <0 or out-of-tile = no-op
    targets = base + jax.lax.broadcasted_iota(jnp.int32, (tile_n, 1), 0)
    onehot = idx == targets                  # (TN, TI)
    ident = _identity(semiring, o_ref.dtype)
    if semiring == "plus_times":
        part = jax.lax.dot_general(
            onehot.astype(o_ref.dtype), val_ref[...].astype(o_ref.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=o_ref.dtype,
        )                                    # (TN, 1) — MXU
    else:
        x = jnp.where(onehot, val_ref[...].astype(o_ref.dtype), ident)
        if semiring in ("min_plus", "min_src"):
            part = jnp.min(x, axis=1, keepdims=True)
        else:
            part = jnp.max(x, axis=1, keepdims=True)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = part

    @pl.when(t != 0)
    def _acc():
        o_ref[...] = _combine_all(semiring, o_ref[...], part)


def scatter_combine_pallas(
    idx: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    semiring: str,
    out_dtype=None,
    tile_n: int = 128,
    tile_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """r[n] = combineAll_{t : idx[t] == n} val[t]; empty n -> identity.

    idx/val: [T]; T % tile_t == 0 and n_out % tile_n == 0 (ops.py pads).
    """
    assert semiring in SEMIRINGS
    (T,) = idx.shape
    assert T % tile_t == 0 and n_out % tile_n == 0, (T, n_out, tile_t, tile_n)
    out_dtype = out_dtype or val.dtype

    grid = (n_out // tile_n, T // tile_t)
    out = pl.pallas_call(
        functools.partial(_scatter_combine_kernel, semiring=semiring, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_t), lambda i, t: (0, t)),
            pl.BlockSpec((1, tile_t), lambda i, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((tile_n, 1), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, 1), out_dtype),
        interpret=interpret,
    )(idx[None, :], val[None, :])
    return out[:, 0]


def _decode_packed_ids(w_ref, t: int, *, width: int, tile_t: int,
                       set_slots: int, n_local: int) -> jnp.ndarray:
    """Decode this tile's bit-packed ids into flat scatter targets (1, TI).

    ``w_ref`` holds tile_t * width / 32 uint32 words, each packing 32/width
    ids LSB-first (codec.pack_uniform).  The decode is pure shift/mask vector
    work — no gather: per-set word alignment (set_slots % ids-per-word == 0)
    makes word index == slot // ids_per_word globally, so a contiguous slot
    tile maps to a contiguous word tile.  Decoded ids are clamped to the
    sentinel ``n_local`` (the per-set drop slot), which also neutralizes any
    padding garbage, then offset into the owning set's segment.
    """
    k = 32 // width
    words = w_ref[...]                                    # (1, TI // k) uint32
    sh = (jax.lax.broadcasted_iota(jnp.uint32, (1, tile_t // k, k), 2)
          * jnp.uint32(width))
    mask = jnp.uint32((1 << width) - 1)
    ids = ((words[..., None] >> sh) & mask).reshape(1, tile_t).astype(jnp.int32)
    g = t * tile_t + jax.lax.broadcasted_iota(jnp.int32, (1, tile_t), 1)
    seg = g // set_slots
    return jnp.minimum(ids, n_local) + seg * (n_local + 1)


def _packed_scatter_kernel(w_ref, val_ref, o_ref, *, semiring: str, tile_n: int,
                           tile_t: int, width: int, set_slots: int, n_local: int):
    """Indexed-payload scatter-combine: the ids arrive BIT-PACKED and are
    decoded in VMEM — the receive side of the packed exchange never
    materializes int32 index rows."""
    t = pl.program_id(1)
    base = pl.program_id(0) * tile_n
    idx = _decode_packed_ids(w_ref, t, width=width, tile_t=tile_t,
                             set_slots=set_slots, n_local=n_local)
    targets = base + jax.lax.broadcasted_iota(jnp.int32, (tile_n, 1), 0)
    onehot = idx == targets                  # (TN, TI)
    ident = _identity(semiring, o_ref.dtype)
    if semiring == "plus_times":
        part = jax.lax.dot_general(
            onehot.astype(o_ref.dtype), val_ref[...].astype(o_ref.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=o_ref.dtype,
        )                                    # (TN, 1) — MXU
    else:
        x = jnp.where(onehot, val_ref[...].astype(o_ref.dtype), ident)
        if semiring in ("min_plus", "min_src"):
            part = jnp.min(x, axis=1, keepdims=True)
        else:
            part = jnp.max(x, axis=1, keepdims=True)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = part

    @pl.when(t != 0)
    def _acc():
        o_ref[...] = _combine_all(semiring, o_ref[...], part)


def packed_scatter_combine_pallas(
    words: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    set_slots: int,
    n_local: int,
    width: int,
    semiring: str,
    out_dtype=None,
    tile_n: int = 128,
    tile_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed-id variant of :func:`scatter_combine_pallas`.

    words: [T * width / 32] uint32; val: [T] payload in static id order;
    slot t of set s targets row decode(t) + s*(n_local+1), s = t // set_slots.
    """
    assert semiring in SEMIRINGS
    (T,) = val.shape
    k = 32 // width
    assert T % tile_t == 0 and n_out % tile_n == 0, (T, n_out, tile_t, tile_n)
    assert tile_t % k == 0 and set_slots % k == 0, (tile_t, set_slots, k)
    assert words.shape == (T // k,), (words.shape, T, k)
    out_dtype = out_dtype or val.dtype

    grid = (n_out // tile_n, T // tile_t)
    out = pl.pallas_call(
        functools.partial(
            _packed_scatter_kernel, semiring=semiring, tile_n=tile_n,
            tile_t=tile_t, width=width, set_slots=set_slots, n_local=n_local),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_t // k), lambda i, t: (0, t)),
            pl.BlockSpec((1, tile_t), lambda i, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((tile_n, 1), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, 1), out_dtype),
        interpret=interpret,
    )(words[None, :], val[None, :])
    return out[:, 0]


def _tropical_columns(semiring: str, onehot, val_t_ref, o_ref, t) -> None:
    """Tropical multi-query combine, one query column at a time.

    ``val_t_ref`` holds the TRANSPOSED (TQ, TI) payload tile: each query is a
    (1, TI) row, masked by the (TN, TI) one-hot and reduced along lanes into
    output column q — the single-query kernel's pattern.  A (TN, TI, TQ)
    broadcast would need a lane-to-sublane relayout Mosaic does not lower."""
    ident = _identity(semiring, o_ref.dtype)
    reduce = jnp.min if semiring in ("min_plus", "min_src") else jnp.max
    for q in range(val_t_ref.shape[0]):
        x = jnp.where(onehot, val_t_ref[q:q + 1, :].astype(o_ref.dtype), ident)
        part = reduce(x, axis=1, keepdims=True)             # (TN, 1)

        @pl.when(t == 0)
        def _init():
            o_ref[:, q:q + 1] = part

        @pl.when(t != 0)
        def _acc():
            o_ref[:, q:q + 1] = _combine_all(semiring, o_ref[:, q:q + 1], part)


def _plus_times_columns(onehot, val_ref, o_ref, t) -> None:
    """plus_times multi-query combine: one (TN, TI) x (TI, TQ) MXU matmul."""
    part = jax.lax.dot_general(
        onehot.astype(o_ref.dtype), val_ref[...].astype(o_ref.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=o_ref.dtype,
    )                                        # (TN, TQ) — MXU at full width

    @pl.when(t == 0)
    def _init():
        o_ref[...] = part

    @pl.when(t != 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


def _multi_combine(semiring: str, onehot, val_ref, o_ref, t) -> None:
    if semiring == "plus_times":
        _plus_times_columns(onehot, val_ref, o_ref, t)
    else:
        _tropical_columns(semiring, onehot, val_ref, o_ref, t)


def _multi_val_spec(semiring: str, tile_t: int, tile_q: int):
    """(transform, BlockSpec) of the [T, Q] payload for the multi kernels:
    plus_times reads (TI, TQ) tiles for the matmul, the tropical semirings
    read the transposed [Q, T] payload as (TQ, TI) tiles."""
    if semiring == "plus_times":
        return (lambda v: v), pl.BlockSpec((tile_t, tile_q), lambda i, q, t: (t, q))
    return (lambda v: v.T), pl.BlockSpec((tile_q, tile_t), lambda i, q, t: (q, t))


def _packed_scatter_multi_kernel(w_ref, val_ref, o_ref, *, semiring: str,
                                 tile_n: int, tile_t: int, width: int,
                                 set_slots: int, n_local: int):
    t = pl.program_id(2)
    base = pl.program_id(0) * tile_n
    idx = _decode_packed_ids(w_ref, t, width=width, tile_t=tile_t,
                             set_slots=set_slots, n_local=n_local)
    targets = base + jax.lax.broadcasted_iota(jnp.int32, (tile_n, 1), 0)
    _multi_combine(semiring, idx == targets, val_ref, o_ref, t)


def packed_scatter_combine_multi_pallas(
    words: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    set_slots: int,
    n_local: int,
    width: int,
    semiring: str,
    out_dtype=None,
    tile_n: int = 128,
    tile_t: int = 128,
    tile_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query packed-id scatter-combine: words [T*width/32], val [T, Q]
    -> r [n_out, Q] (the serving wire format with bit-packed structure)."""
    assert semiring in SEMIRINGS
    T, Q = val.shape
    k = 32 // width
    assert T % tile_t == 0 and n_out % tile_n == 0 and Q % tile_q == 0, (
        T, n_out, Q, tile_t, tile_n, tile_q)
    assert tile_t % k == 0 and set_slots % k == 0, (tile_t, set_slots, k)
    assert words.shape == (T // k,), (words.shape, T, k)
    out_dtype = out_dtype or val.dtype

    grid = (n_out // tile_n, Q // tile_q, T // tile_t)
    layout, val_spec = _multi_val_spec(semiring, tile_t, tile_q)
    return pl.pallas_call(
        functools.partial(
            _packed_scatter_multi_kernel, semiring=semiring, tile_n=tile_n,
            tile_t=tile_t, width=width, set_slots=set_slots, n_local=n_local),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_t // k), lambda i, q, t: (0, t)),
            val_spec,
        ],
        out_specs=pl.BlockSpec((tile_n, tile_q), lambda i, q, t: (i, q)),
        out_shape=jax.ShapeDtypeStruct((n_out, Q), out_dtype),
        interpret=interpret,
    )(words[None, :], layout(val))


def _scatter_combine_multi_kernel(idx_ref, val_ref, o_ref, *, semiring: str, tile_n: int):
    t = pl.program_id(2)
    base = pl.program_id(0) * tile_n
    targets = base + jax.lax.broadcasted_iota(jnp.int32, (tile_n, 1), 0)
    _multi_combine(semiring, idx_ref[...] == targets, val_ref, o_ref, t)


def scatter_combine_multi_pallas(
    idx: jnp.ndarray,
    val: jnp.ndarray,
    n_out: int,
    *,
    semiring: str,
    out_dtype=None,
    tile_n: int = 128,
    tile_t: int = 128,
    tile_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query scatter-combine: idx [T], val [T, Q] -> r [n_out, Q] (the
    serving wire format — Q values ride each shipped index).  plus_times is
    a pure MXU matmul; the tropical semirings reduce one query column at a
    time.  tile_q is the full Q or a multiple of 128 (lane blocks)."""
    assert semiring in SEMIRINGS
    T, Q = val.shape
    assert idx.shape == (T,), (idx.shape, val.shape)
    assert T % tile_t == 0 and n_out % tile_n == 0 and Q % tile_q == 0, (
        T, n_out, Q, tile_t, tile_n, tile_q)
    out_dtype = out_dtype or val.dtype

    grid = (n_out // tile_n, Q // tile_q, T // tile_t)
    layout, val_spec = _multi_val_spec(semiring, tile_t, tile_q)
    return pl.pallas_call(
        functools.partial(_scatter_combine_multi_kernel, semiring=semiring, tile_n=tile_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_t), lambda i, q, t: (0, t)),
            val_spec,
        ],
        out_specs=pl.BlockSpec((tile_n, tile_q), lambda i, q, t: (i, q)),
        out_shape=jax.ShapeDtypeStruct((n_out, Q), out_dtype),
        interpret=interpret,
    )(idx[None, :], layout(val))
