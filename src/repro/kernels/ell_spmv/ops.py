"""Jit'd wrapper for the ELL GIM-V kernel + ELL building from edge lists.

ELL tables are SLOT-MAJOR: cols / w are [..., D, R] (D degree slots of R
destination rows; col < 0 pads), the layout the kernel tiles directly.  They
are packed that way once, at pre-partition time (``ell_from_edges`` and the
``repro.core.blocks`` packers, whose widths and row counts follow
``ell_width`` / ``ell_rows``), so a kernel call runs only the value gather
before the launch — no transpose or pad of the constant tables per call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ell_spmv.ell_spmv import ell_gimv_multi_pallas, ell_gimv_pallas

__all__ = ["ell_gimv", "ell_gimv_multi", "ell_from_edges", "ell_width", "ell_rows"]

# A table up to one lane tile wide or tall is one full-dimension tile; larger
# ones tile at multiples of 128.
_LANE = 128


def _lane_align(x: int) -> int:
    return x if x <= _LANE else -(-x // _LANE) * _LANE


def ell_width(d: int) -> int:
    """Slot count D a table of max degree ``d`` is packed at: d itself up to
    128 slots (narrow degree buckets stay narrow), else a multiple of 128."""
    return _lane_align(max(int(d), 1))


def ell_rows(r: int) -> int:
    """Row count R a table of ``r`` destination rows is packed at: r itself
    up to 128 rows, else a multiple of 128 (pad rows hold col -1)."""
    return _lane_align(int(r))


def ell_from_edges(dst: np.ndarray, src: np.ndarray, w: np.ndarray | None, n_rows: int,
                   *, d_cap: int | None = None):
    """Edge list -> slot-major ELL (cols[D, n_rows], w[D, n_rows]); col<0 pads.

    D = ell_width(max in-degree), or ``d_cap`` when given (so stripes packed
    per worker can stack).  Vectorized (stable sort + offset-from-row-start
    slots) so pre-partition-time packing of web-scale stripes stays
    O(E log E), not a Python loop.  Slot order within a row is edge
    submission order.
    """
    dst = np.asarray(dst, dtype=np.int64)
    src = np.asarray(src, dtype=np.int64)
    deg = np.bincount(dst, minlength=n_rows)
    D = ell_width(deg.max(initial=0))
    if d_cap is not None:
        assert d_cap >= int(deg.max(initial=0)), (d_cap, int(deg.max(initial=0)))
        D = d_cap
    order = np.argsort(dst, kind="stable")
    dst_s, src_s = dst[order], src[order]
    starts = np.concatenate([[0], np.cumsum(deg)])
    slots = np.arange(len(dst_s), dtype=np.int64) - starts[dst_s]
    cols = np.full((D, n_rows), -1, dtype=np.int32)
    cols[slots, dst_s] = src_s
    ww = None
    if w is not None:
        ww = np.zeros((D, n_rows), dtype=np.float32)
        ww[slots, dst_s] = np.asarray(w)[order]
    return cols, ww


# Elements of one (TD, TR) table tile: three f32/int32 operands, double
# buffered, stay at ~3 MiB of VMEM.
_TILE_ELEMS = 1 << 17


def _largest_divisor(n: int, at_most: int) -> int:
    return max(k for k in range(1, min(n, at_most) + 1) if n % k == 0)


def _tiles(R: int, D: int, q: int = 1) -> tuple[int, int, int, int]:
    """(tile_r, tile_d, Rp, Dp) for a [D, R] table gathered q queries wide.

    Rp / Dp are R / D lane-aligned as the packers align them (so a packed
    table needs no pad).  Row tiles are lane multiples of 128 dividing Rp,
    as wide as the element budget allows."""
    Dp, Rp = ell_width(D), ell_rows(R)
    tile_d = min(Dp, _LANE)
    if Rp <= _LANE:
        return Rp, tile_d, Rp, Dp
    per_row = max(-(-tile_d // 8) * 8, 8) * q      # sublane-padded elements
    k_max = max(1, min(64, _TILE_ELEMS // (per_row * _LANE)))
    return _LANE * _largest_divisor(Rp // _LANE, k_max), tile_d, Rp, Dp


def _batched(cols, w, Rp: int, Dp: int):
    """[*L, D, R] tables -> [L, Dp, Rp]; pads (col -1, weight 0) only for a
    table the packers did not align."""
    D, R = cols.shape[-2:]
    cols = cols.reshape((-1, D, R))
    w = None if w is None else w.reshape((-1, D, R))
    if (Dp, Rp) != (D, R):
        pad = ((0, 0), (0, Dp - D), (0, Rp - R))
        cols = jnp.pad(cols, pad, constant_values=-1)
        w = None if w is None else jnp.pad(w, pad)
    return cols, w


@partial(jax.jit, static_argnames=("semiring", "interpret"))
def ell_gimv(
    cols: jnp.ndarray,
    w: jnp.ndarray | None,
    v: jnp.ndarray,
    *,
    semiring: str,
    interpret: bool = False,
) -> jnp.ndarray:
    """ELL GIM-V: r[..., i] = combineAll_d combine2(w[..., d, i], v[cols[..., d, i]]).

    cols/w: [*L, D, R] slot-major (leading axes batch tables of one shape
    into one launch); v: [N] -> r: [*L, R].  The source gather runs in XLA;
    the kernel does combine2 + combineAll."""
    lead, (D, R) = cols.shape[:-2], cols.shape[-2:]
    tile_r, tile_d, Rp, Dp = _tiles(R, D)
    cols3, w3 = _batched(cols, w, Rp, Dp)
    vals = v[jnp.maximum(cols3, 0)]                        # [L, Dp, Rp]
    out = ell_gimv_pallas(
        cols3, w3, vals, semiring=semiring, out_dtype=v.dtype,
        tile_r=tile_r, tile_d=tile_d, interpret=interpret,
    )
    return out[:, :R].reshape(lead + (R,))


# Gathered elements per multi-query launch: L x Q x D x R values plus their
# flat indices are materialized in HBM, so larger tables run in row chunks
# of at most this many elements (128 MiB of f32 values).
_GATHER_CHUNK_ELEMS = 1 << 25


@partial(jax.jit, static_argnames=("semiring", "interpret"))
def ell_gimv_multi(
    cols: jnp.ndarray,
    w: jnp.ndarray | None,
    v: jnp.ndarray,
    *,
    semiring: str,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-query ELL GIM-V: cols/w: [*L, D, R]; v: [N, Q] (one query per
    column) -> r: [*L, R, Q].  Queries pad to a multiple of 8 and ride a
    leading tile axis, so Q never constrains the lane layout; tables whose
    gathered values exceed _GATHER_CHUNK_ELEMS run in sequential row chunks."""
    lead, (D, R) = cols.shape[:-2], cols.shape[-2:]
    N, Q = v.shape
    tile_q = 8
    Qp = -(-Q // tile_q) * tile_q
    tile_r, tile_d, Rp, Dp = _tiles(R, D, tile_q)
    cols3, w3 = _batched(cols, w, Rp, Dp)
    L = cols3.shape[0]
    n_tiles = Rp // tile_r
    chunk = tile_r * _largest_divisor(
        n_tiles, max(1, _GATHER_CHUNK_ELEMS // (L * Qp * Dp * tile_r)))
    # a scalar gather from the flattened [Qp * N] vector keeps Q ahead of
    # the table axes; gathering Q-wide rows would put Q on the minor axis,
    # padded to 128 lanes.
    v_flat = jnp.pad(v.T, ((0, Qp - Q), (0, 0))).reshape(-1)   # [Qp * N]
    q_off = (jnp.arange(Qp, dtype=jnp.int32) * N)[:, None, None]

    def run(c, w_c):
        vals = v_flat[jnp.maximum(c, 0)[:, None] + q_off]     # [L, Qp, Dp, Rc]
        return ell_gimv_multi_pallas(
            c, w_c, vals, semiring=semiring, out_dtype=v.dtype,
            tile_r=tile_r, tile_d=tile_d, tile_q=tile_q, interpret=interpret)

    if chunk == Rp:
        out = run(cols3, w3)
    else:
        def body(k, acc):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, k * chunk, chunk, axis=2)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, run(sl(cols3), None if w3 is None else sl(w3)),
                k * chunk, axis=2)

        out = jax.lax.fori_loop(0, Rp // chunk, body,
                                jnp.zeros((L, Qp, Rp), v.dtype))
    out = jnp.swapaxes(out[:, :Q, :R], 1, 2)                # [L, R, Q]
    return out.reshape(lead + (R, Q))
