"""Pallas TPU kernel: dense-region block GIM-V (the paper's M_d (x) v_d).

PMV_hybrid executes the dense region (columns of high-out-degree vertices)
horizontally: every worker holds the gathered dense sub-vector v_d and its
dense row stripe.  When that stripe is materialized as an actual dense
matrix (rows = local vertices, cols = compacted dense slots), the semiring
"matvec" is a classic MXU/VPU tiling problem:

- (x, +)  [PageRank / RWR]: real matmul -> `jnp.dot` on the MXU.
- (+, min) [SSSP]:          broadcast-add + row-min on the VPU.
- (src, min) [CC]:          presence-masked select + row-min on the VPU.

Grid = (row_tiles, col_tiles); the output row tile is revisited along the
col grid axis and accumulated in place with the semiring's combineAll —
the standard TPU reduction pattern (output VMEM block as accumulator).
Tiles are MXU/VPU aligned: TM rows x TK cols, both multiples of 128 (8 is
the sublane minimum for f32; we use 128 to keep the MXU fed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SEMIRINGS = ("plus_times", "min_plus", "min_src", "max_plus")


def _combine_all(semiring: str, a, b):
    if semiring == "plus_times":
        return a + b
    if semiring in ("min_plus", "min_src"):
        return jnp.minimum(a, b)
    return jnp.maximum(a, b)


def _identity(semiring: str, dtype):
    if semiring == "plus_times":
        return jnp.zeros((), dtype)
    if semiring in ("min_plus", "min_src"):
        return jnp.array(jnp.inf if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).max, dtype)
    return jnp.array(-jnp.inf if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).min, dtype)


def _dense_tropical_part(semiring: str, m, v_row, out_dtype):
    """(TM, 1) partial combineAll of a (TM, TK) tile against a (1, TK) row."""
    if semiring == "min_plus":
        return jnp.min(m + v_row, axis=1, keepdims=True)
    if semiring == "max_plus":
        return jnp.max(m + v_row, axis=1, keepdims=True)
    # min_src: m is a presence indicator; absent -> identity
    x = jnp.where(m > 0, v_row.astype(out_dtype), _identity(semiring, out_dtype))
    return jnp.min(x, axis=1, keepdims=True)


def _dense_gimv_kernel(m_ref, v_ref, o_ref, *, semiring: str):
    """One (TM, TK) tile: partial combineAll over the TK columns."""
    k = pl.program_id(1)
    m = m_ref[...]                      # (TM, TK) matrix values
    v = v_ref[...]                      # (1, TK) vector tile

    if semiring == "plus_times":
        part = jax.lax.dot_general(
            m, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=o_ref.dtype,
        )                               # (TM, 1) — MXU, f32 passes
    else:
        part = _dense_tropical_part(semiring, m, v, o_ref.dtype)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part.astype(o_ref.dtype)

    @pl.when(k != 0)
    def _acc():
        o_ref[...] = _combine_all(semiring, o_ref[...], part.astype(o_ref.dtype))


def _dense_gimv_multi_kernel(m_ref, v_ref, o_ref, *, semiring: str):
    """One (TM, TK) matrix tile against TQ queries: partial combineAll over
    the TK columns.

    plus_times is a straight MXU matmul of the (TK, TQ) query tile.  The
    tropical semirings read the TRANSPOSED (TQ, TK) tile and reduce one
    query row at a time into output column q, the single-query pattern: a
    (TM, TK, TQ) broadcast would need a lane-to-sublane relayout.
    """
    k = pl.program_id(2)
    m = m_ref[...]                      # (TM, TK) matrix values

    if semiring == "plus_times":
        part = jax.lax.dot_general(
            m, v_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=o_ref.dtype,
        )                               # (TM, TQ) — MXU at full width

        @pl.when(k == 0)
        def _init():
            o_ref[...] = part.astype(o_ref.dtype)

        @pl.when(k != 0)
        def _acc():
            o_ref[...] = o_ref[...] + part.astype(o_ref.dtype)
        return

    for q in range(v_ref.shape[0]):
        part = _dense_tropical_part(semiring, m, v_ref[q:q + 1, :], o_ref.dtype)

        @pl.when(k == 0)
        def _init():
            o_ref[:, q:q + 1] = part.astype(o_ref.dtype)

        @pl.when(k != 0)
        def _acc():
            o_ref[:, q:q + 1] = _combine_all(semiring, o_ref[:, q:q + 1],
                                             part.astype(o_ref.dtype))


def dense_gimv_multi_pallas(
    m: jnp.ndarray,
    v: jnp.ndarray,
    *,
    semiring: str,
    out_dtype=None,
    tile_m: int = 128,
    tile_k: int = 128,
    tile_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query semiring matmul r = M (x) V over a dense block.

    m: [M, K] (values; for min_src a presence matrix), v: [K, Q] — one query
    per column.  The grid gains a query-tile axis so the MXU (plus_times) /
    VPU (tropical) is fed TQ queries wide per pass over the resident matrix
    tile — the batched-serving analog of dense_gimv_pallas.  M, K, Q must be
    multiples of the tile sizes (ops.py pads); tile_q is the full Q or a
    multiple of 128 (lane blocks).  Returns r: [M, Q].
    """
    assert semiring in SEMIRINGS, semiring
    M, K = m.shape
    K2, Q = v.shape
    assert K2 == K, (m.shape, v.shape)
    assert M % tile_m == 0 and K % tile_k == 0 and Q % tile_q == 0, (
        M, K, Q, tile_m, tile_k, tile_q)
    out_dtype = out_dtype or v.dtype

    grid = (M // tile_m, Q // tile_q, K // tile_k)  # k innermost: accumulate
    if semiring == "plus_times":
        v_spec = pl.BlockSpec((tile_k, tile_q), lambda i, q, k: (k, q))
    else:
        v, v_spec = v.T, pl.BlockSpec((tile_q, tile_k), lambda i, q, k: (q, k))
    return pl.pallas_call(
        functools.partial(_dense_gimv_multi_kernel, semiring=semiring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda i, q, k: (i, k)),
            v_spec,
        ],
        out_specs=pl.BlockSpec((tile_m, tile_q), lambda i, q, k: (i, q)),
        out_shape=jax.ShapeDtypeStruct((M, Q), out_dtype),
        interpret=interpret,
    )(m, v)


def dense_gimv_pallas(
    m: jnp.ndarray,
    v: jnp.ndarray,
    *,
    semiring: str,
    out_dtype=None,
    tile_m: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """r = combineAll_j combine2(m[:, j], v[j]) over a dense block.

    m: [M, K] (values; for min_src a presence matrix), v: [K].
    M, K must be multiples of the tile sizes (ops.py pads).
    Returns r: [M].
    """
    assert semiring in SEMIRINGS, semiring
    M, K = m.shape
    assert v.shape == (K,), (m.shape, v.shape)
    assert M % tile_m == 0 and K % tile_k == 0, (M, K, tile_m, tile_k)
    out_dtype = out_dtype or v.dtype

    grid = (M // tile_m, K // tile_k)
    out = pl.pallas_call(
        functools.partial(_dense_gimv_kernel, semiring=semiring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda i, k: (i, k)),
            pl.BlockSpec((1, tile_k), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((tile_m, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), out_dtype),
        interpret=interpret,
    )(m, v[None, :])
    return out[:, 0]
