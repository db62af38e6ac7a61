"""Pallas TPU kernel: sparse-region GIM-V over ELL (padded neighbor lists).

The paper's sparse region M_s^(i,j) is a low-density edge block.  The
TPU-native layout is ELL: each destination row stores up to D source slots
(cols[d, r], w[d, r]; col < 0 marks padding).

The data-dependent read ``v[cols]`` is done by XLA before the kernel (ops.py):
Mosaic lowers only 2-D gathers inside a kernel, and a gather source of the
whole sub-vector would also have to sit in VMEM, which a real n_local
(2^22 / 16 vertices, 1 MiB of f32 per worker block, b of them in emulation)
does not fit.  The kernel therefore reads three aligned tiles — the gathered
values, the weights and the padding mask — and does combine2 + combineAll.

Tables are SLOT-MAJOR, [L, D, R] (ops.py): destination rows run along the
128 lanes and the degree slots along sublanes, so the combineAll over slots
is a sublane reduction and each output tile is a lane-dense (1, TR) row.  An
[R, 1] column output would be padded to 128 lanes in HBM.  The leading axis
batches tables of one shape (blocks, workers) into one launch.

Grid = (tables, row_tiles, deg_tiles); the deg axis accumulates into the
output tile with the semiring combineAll, identical to the dense kernel's
pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_gimv.block_gimv import SEMIRINGS, _combine_all, _identity


def _combine2_reduce(semiring: str, cols, w, vals, out_dtype, axis: int,
                     keepdims: bool = False):
    """combine2(w, vals) with pads (cols < 0) set to the identity, then the
    combineAll over ``axis`` (the degree-slot axis of the tile)."""
    valid = cols >= 0
    if semiring == "plus_times":
        x = w * vals if w is not None else vals
    elif semiring in ("min_plus", "max_plus"):
        x = w + vals if w is not None else vals
    else:  # min_src
        x = vals
    x = jnp.where(valid, x.astype(out_dtype), _identity(semiring, out_dtype))
    if semiring == "plus_times":
        return jnp.sum(x, axis=axis, keepdims=keepdims)
    if semiring in ("min_plus", "min_src"):
        return jnp.min(x, axis=axis, keepdims=keepdims)
    return jnp.max(x, axis=axis, keepdims=keepdims)


def _accumulate(o_ref, part, semiring: str, d):
    @pl.when(d == 0)
    def _init():
        o_ref[...] = part

    @pl.when(d != 0)
    def _acc():
        o_ref[...] = _combine_all(semiring, o_ref[...], part)


def _ell_gimv_kernel(cols_ref, w_ref, vals_ref, o_ref, *, semiring: str, has_w: bool):
    """One (TD, TR) tile: slots along sublanes, destination rows along lanes."""
    w = w_ref[...] if has_w else None
    part = _combine2_reduce(semiring, cols_ref[...], w, vals_ref[...],
                            o_ref.dtype, axis=0, keepdims=True)   # (1, TR)
    _accumulate(o_ref, part, semiring, pl.program_id(2))


def ell_gimv_pallas(
    cols_t: jnp.ndarray,
    w_t: jnp.ndarray | None,
    vals_t: jnp.ndarray,
    *,
    semiring: str,
    out_dtype=None,
    tile_r: int = 512,
    tile_d: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """r[l, i] = combineAll_d combine2(w[l, d, i], vals[l, d, i]), pads
    (col<0) skipped.

    cols_t / w_t / vals_t: [L, D, R] slot-major tables, vals_t the gathered
    source values.  R % tile_r == 0 and D % tile_d == 0 (ops.py).  Returns
    r: [L, R].
    """
    assert semiring in SEMIRINGS
    L, D, R = cols_t.shape
    assert R % tile_r == 0 and D % tile_d == 0, (R, D, tile_r, tile_d)
    out_dtype = out_dtype or vals_t.dtype
    has_w = w_t is not None
    blk = pl.BlockSpec((pl.squeezed, tile_d, tile_r), lambda l, i, d: (l, d, i))
    args = (cols_t, w_t, vals_t) if has_w else (cols_t, vals_t)
    kernel = functools.partial(_ell_gimv_kernel, semiring=semiring, has_w=has_w)
    if not has_w:
        kernel = _drop_w(kernel)
    out = pl.pallas_call(
        kernel,
        grid=(L, R // tile_r, D // tile_d),
        in_specs=[blk] * len(args),
        out_specs=pl.BlockSpec((pl.squeezed, 1, tile_r), lambda l, i, d: (l, 0, i)),
        out_shape=jax.ShapeDtypeStruct((L, 1, R), out_dtype),
        interpret=interpret,
    )(*args)
    return out[:, 0]


def _drop_w(kernel):
    """Adapt a (cols, w, vals, out) kernel to a call without the w operand."""
    def k(cols_ref, vals_ref, o_ref):
        return kernel(cols_ref, None, vals_ref, o_ref)
    return k


def _ell_gimv_multi_kernel(cols_ref, w_ref, vals_ref, o_ref, *, semiring: str,
                           has_w: bool):
    """Multi-query tile: (TQ, TD, TR) gathered values share one (TD, TR)
    cols / w tile, so the query axis never touches the lane dimension."""
    w = w_ref[...][None] if has_w else None
    part = _combine2_reduce(semiring, cols_ref[...][None], w, vals_ref[...],
                            o_ref.dtype, axis=1)
    _accumulate(o_ref, part, semiring, pl.program_id(3))


def ell_gimv_multi_pallas(
    cols_t: jnp.ndarray,
    w_t: jnp.ndarray | None,
    vals_t: jnp.ndarray,
    *,
    semiring: str,
    out_dtype=None,
    tile_r: int = 512,
    tile_d: int = 128,
    tile_q: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """r[l, q, i] = combineAll_d combine2(w[l, d, i], vals[l, q, d, i]);
    pads skipped.

    cols_t / w_t: [L, D, R]; vals_t: [L, Q, D, R] gathered per query.
    R % tile_r == D % tile_d == Q % tile_q == 0 (ops.py).  Grid =
    (L, row_tiles, query_tiles, deg_tiles) with the deg axis innermost so
    the output tile accumulates in place.  Returns r: [L, Q, R].
    """
    assert semiring in SEMIRINGS
    L, D, R = cols_t.shape
    Q = vals_t.shape[1]
    assert vals_t.shape == (L, Q, D, R), (vals_t.shape, cols_t.shape)
    assert R % tile_r == 0 and D % tile_d == 0 and Q % tile_q == 0, (
        R, D, Q, tile_r, tile_d, tile_q)
    out_dtype = out_dtype or vals_t.dtype
    has_w = w_t is not None
    tbl = pl.BlockSpec((pl.squeezed, tile_d, tile_r), lambda l, i, q, d: (l, d, i))
    in_specs = [tbl] + ([tbl] if has_w else []) + [
        pl.BlockSpec((pl.squeezed, tile_q, tile_d, tile_r),
                     lambda l, i, q, d: (l, q, d, i))]
    args = (cols_t, w_t, vals_t) if has_w else (cols_t, vals_t)
    kernel = functools.partial(_ell_gimv_multi_kernel, semiring=semiring, has_w=has_w)
    if not has_w:
        kernel = _drop_w(kernel)
    return pl.pallas_call(
        kernel,
        grid=(L, R // tile_r, Q // tile_q, D // tile_d),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((pl.squeezed, tile_q, tile_r),
                               lambda l, i, q, d: (l, q, i)),
        out_shape=jax.ShapeDtypeStruct((L, Q, R), out_dtype),
        interpret=interpret,
    )(*args)
