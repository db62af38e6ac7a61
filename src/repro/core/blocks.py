"""Static-shape block-matrix layouts for pre-partitioned GIM-V.

The paper partitions M into b x b sub-matrices M^(i,j).  On TPU we need
static shapes, so a *stripe* (the b blocks co-located on one worker) is stored
as arrays of shape [b, E_cap] padded to the max per-block edge count:

- ``seg_local``: the *segment* (combineAll target) local vertex index — the
  destination p_local.
- ``gat_local``: the *gather* (combine2 input) local vertex index — the source
  q_local (or, for hybrid dense regions, the slot into the compacted dense
  vector).
- ``w``: matrix values m_{p,q} (None when the spec never reads them, e.g. CC).
- ``count``: per-block edge counts (mask = arange(E_cap) < count[k]).

The same structure serves both placements; only the meaning of the leading
block axis differs:

- vertical stripe on worker j: leading axis = destination block i; gat_local
  indexes the *local* sub-vector v^(j).
- horizontal stripe on worker i: leading axis = source block jj; gat_local
  indexes v^(jj) out of the all-gathered vector.

All indices are int32 (local indices stay < n_local ~ |v|/b even at
ClueWeb12 scale: 6.2e9 / 512 = 12.2M), which is why the layout is blocked
rather than flat: flat global ids would overflow int32 at |v| > 2^31.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np

from repro.kernels.ell_spmv import ell_from_edges, ell_rows, ell_width

__all__ = [
    "BlockEdges",
    "build_stripes",
    "DenseRegion",
    "EllStripe",
    "stripe_to_ell",
    "stack_ells",
    "materialize_dense_matrix",
    "materialize_dense_block",
    "EllBucket",
    "DenseGroup",
    "PlannedStripe",
    "pack_bucketed_ell",
    "pack_planned_stripe",
    "pack_streamed_stripe",
    "stack_planned",
    "stack_streamed",
    "planned_to_edges",
]


@dataclasses.dataclass(frozen=True)
class BlockEdges:
    """One worker's stripe of b edge blocks, padded to a common capacity.

    Arrays may be numpy (host, right after partitioning) or jnp (on device).
    When used under shard_map, arrays carry an extra leading worker axis
    [b_workers, b, E_cap] that shard_map splits.
    """

    seg_local: Any   # [b, E_cap] int32
    gat_local: Any   # [b, E_cap] int32
    w: Any | None    # [b, E_cap] f32, or None
    count: Any       # [b] int32

    @property
    def e_cap(self) -> int:
        return self.seg_local.shape[-1]

    def astuple(self):
        return (self.seg_local, self.gat_local, self.w, self.count)


jax.tree_util.register_dataclass(
    BlockEdges,
    data_fields=["seg_local", "gat_local", "w", "count"],
    meta_fields=[],
)


def _pad_to(arr: np.ndarray, length: int, fill) -> np.ndarray:
    out = np.full((length,), fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def build_stripes(
    seg_block: np.ndarray,
    seg_local: np.ndarray,
    gat_block: np.ndarray,
    gat_local: np.ndarray,
    w: np.ndarray | None,
    b: int,
    *,
    stripe_axis: str,
) -> tuple[list[BlockEdges], np.ndarray]:
    """Group edges into per-worker stripes of per-block padded arrays.

    stripe_axis='gat': vertical placement — worker owns all edges whose
      *source* (gather side) lives in its block; the inner block axis is the
      segment (destination) block.
    stripe_axis='seg': horizontal placement — worker owns all edges whose
      *destination* (segment side) lives in its block; the inner block axis is
      the gather (source) block.

    Returns (stripes[worker], block_nnz[b_inner, b_worker-ish]) where
    block_nnz[i, j] = edges in sub-matrix M^(i,j) (i = seg block, j = gat
    block) — the input of capacity sizing and cost-model validation.
    """
    assert stripe_axis in ("gat", "seg")
    owner = gat_block if stripe_axis == "gat" else seg_block
    inner = seg_block if stripe_axis == "gat" else gat_block

    # Per-(owner, inner) counts -> E_cap.
    pair = owner.astype(np.int64) * b + inner.astype(np.int64)
    counts2d = np.bincount(pair, minlength=b * b).reshape(b, b)  # [owner, inner]
    e_cap = max(int(counts2d.max()), 1)

    # Sort edges by (owner, inner, seg_local) so segment ids are sorted
    # within each block (enables indices_are_sorted=True downstream).  One
    # stable argsort of the packed int64 key orders exactly as a lexsort of
    # the three keys.
    seg_span = int(seg_local.max(initial=0)) + 1
    order = np.argsort(pair * seg_span + seg_local, kind="stable")
    seg_local = seg_local[order]
    gat_local = gat_local[order]
    ww = None if w is None else w[order]

    # Split points per (owner, inner) in the sorted order.
    boundaries = np.searchsorted(pair[order], np.arange(b * b + 1))

    stripes: list[BlockEdges] = []
    for j in range(b):
        seg_blocks = np.zeros((b, e_cap), dtype=np.int32)
        gat_blocks = np.zeros((b, e_cap), dtype=np.int32)
        w_blocks = None if w is None else np.zeros((b, e_cap), dtype=w.dtype)
        cnt = np.zeros((b,), dtype=np.int32)
        for i in range(b):
            lo, hi = boundaries[j * b + i], boundaries[j * b + i + 1]
            m = hi - lo
            cnt[i] = m
            if m:
                seg_blocks[i, :m] = seg_local[lo:hi]
                gat_blocks[i, :m] = gat_local[lo:hi]
                if w_blocks is not None:
                    w_blocks[i, :m] = ww[lo:hi]
        stripes.append(BlockEdges(seg_blocks, gat_blocks, w_blocks, cnt))

    if stripe_axis == "gat":
        block_nnz = counts2d.T  # -> [seg block i, gat block j]
    else:
        block_nnz = counts2d   # already [seg i, gat jj]... owner==seg here
    return stripes, block_nnz


def structural_partial_nnz(
    seg_block: np.ndarray, seg_local: np.ndarray, gat_block: np.ndarray, b: int
) -> np.ndarray:
    """nnz_struct[i, j] = |{distinct p_local : (p, q) in M^(i,j)}|.

    This is the exact structural size of the partial result vector v^(i,j) in
    PMV_vertical (paper Eq. 4 estimates its expectation); it sizes the static
    capacity of the sparse exchange so overflow can never occur.
    """
    key = (seg_block.astype(np.int64) * b + gat_block.astype(np.int64)) * (
        int(seg_local.max(initial=0)) + 1
    ) + seg_local.astype(np.int64)
    uniq = np.unique(key)
    pair = uniq // (int(seg_local.max(initial=0)) + 1)
    counts = np.bincount(pair, minlength=b * b)
    return counts.reshape(b, b)


@dataclasses.dataclass(frozen=True)
class EllStripe:
    """ELL repack of a :class:`BlockEdges` stripe for the Pallas kernels
    (backend='pallas'): each destination row stores up to D source slots;
    col < 0 marks padding.  Tables are slot-major ([D, rows], the kernel's
    layout, see kernels.ell_spmv.ops).

    Two layouts, produced at pre-partition time (stripe_to_ell):

    - per-block (vertical stripes): cols [b, D, n_local] — column r of table
      i lists the v^(j)-local sources of destination r in sub-matrix
      M^(i,j); the kernel runs one table per destination block (partials
      stay separable for the compact exchange).
    - merged (horizontal stripes): cols [D, n_local] — all b source blocks'
      edges of destination r in ONE column, cols pre-offset to index the
      flat gathered vector [b * stride]; the kernel's combineAll over D is
      then also the cross-block combineAll, so one kernel call does the
      whole per-worker compute.
    """

    cols: Any        # [(b,) D, n_local] int32; -1 = pad
    w: Any | None    # matching weights, or None when the spec never reads them

    @property
    def d_cap(self) -> int:
        return self.cols.shape[-2]


jax.tree_util.register_dataclass(
    EllStripe,
    data_fields=["cols", "w"],
    meta_fields=[],
)


def _pack_ell(dst, src, w, n_rows: int, d_cap: int | None = None):
    """Edge arrays -> slot-major (cols [D, n_rows], w [D, n_rows]); the
    kernel package's vectorized packer (kernels do not import core, so no
    cycle)."""
    return ell_from_edges(dst, src, w, n_rows, d_cap=d_cap)


def stripe_to_ell(
    stripe: BlockEdges,
    n_rows: int,
    *,
    merge_col_stride: int | None = None,
    d_cap: int | None = None,
) -> EllStripe:
    """Repack a padded edge-block stripe into ELL neighbor tables.

    merge_col_stride=None: per-block tables [b, D, n_local] (cols are the
    block-local gather indices, as stored).  merge_col_stride=s: one merged
    table [D, n_local] whose cols are flattened to block_k * s + gat_local —
    the layout ``gathered_gimv``'s flat all-gathered vector wants.
    """
    b, _ = stripe.seg_local.shape
    counts = np.asarray(stripe.count)
    seg = np.asarray(stripe.seg_local)
    gat = np.asarray(stripe.gat_local)
    has_w = stripe.w is not None
    www = np.asarray(stripe.w) if has_w else None

    def block_edges(k):
        cnt = int(counts[k])
        return seg[k, :cnt], gat[k, :cnt], (www[k, :cnt] if has_w else None)

    if merge_col_stride is not None:
        dsts, srcs, ws = [], [], []
        for k in range(b):
            d_k, s_k, w_k = block_edges(k)
            dsts.append(d_k)
            srcs.append(s_k.astype(np.int64) + k * merge_col_stride)
            if has_w:
                ws.append(w_k)
        cols, ww = _pack_ell(
            np.concatenate(dsts) if dsts else np.zeros(0, np.int64),
            np.concatenate(srcs) if srcs else np.zeros(0, np.int64),
            np.concatenate(ws) if has_w else None,
            n_rows, d_cap)
        return EllStripe(cols=cols, w=ww)

    if d_cap is None:
        d_cap = 1
        for k in range(b):
            cnt = int(counts[k])
            if cnt:
                deg = np.bincount(seg[k, :cnt], minlength=n_rows)
                d_cap = max(d_cap, int(deg.max()))
        d_cap = ell_width(d_cap)
    tables = [_pack_ell(*block_edges(k), n_rows, d_cap) for k in range(b)]
    cols = np.stack([t[0] for t in tables])
    ww = np.stack([t[1] for t in tables]) if has_w else None
    return EllStripe(cols=cols, w=ww)


def stack_ells(ells: list[EllStripe]) -> EllStripe:
    """b per-worker ELL tables -> one stripe with a leading worker axis,
    padded to the max neighbor-table width across workers."""
    d = max(e.d_cap for e in ells)

    def pad(e: EllStripe):
        widths = [(0, 0)] * (e.cols.ndim - 2) + [(0, d - e.d_cap), (0, 0)]
        cols = np.pad(e.cols, widths, constant_values=-1)
        w = None if e.w is None else np.pad(e.w, widths)
        return cols, w

    padded = [pad(e) for e in ells]
    cols = np.stack([c for c, _ in padded])
    w = None if ells[0].w is None else np.stack([w_ for _, w_ in padded])
    return EllStripe(cols=cols, w=w)


# Semiring fill value (no-op under combineAll) and the fold used when
# parallel edges land on the same dense cell — matching segment_combine on
# the edge list.  min_src stores a presence matrix (fill 0, fold max).
SEMIRING_FILL_FOLD = {
    "plus_times": (0.0, np.add),
    "min_plus": (np.inf, np.minimum),
    "max_plus": (-np.inf, np.maximum),
    "min_src": (0.0, np.maximum),
}


def materialize_dense_matrix(
    stripe: BlockEdges, n_local: int, d_cap: int, semiring: str
) -> np.ndarray:
    """Dense-region horizontal stripe -> an actual [n_local, b * d_cap] dense
    matrix for the MXU kernels (dense_gimv / dense_gimv_multi).

    Column jj * d_cap + slot holds the combine2 weight of the edge from dense
    slot ``slot`` of block jj; absent entries hold the semiring's padding
    value (0 / +-inf / presence 0) so they are no-ops under combineAll.
    Parallel edges fold with the semiring's own combine (sum / min / max /
    presence), matching what segment_combine does on the edge list.
    """
    b, _ = stripe.seg_local.shape
    counts = np.asarray(stripe.count)
    fill, fold = SEMIRING_FILL_FOLD[semiring]
    m = np.full((n_local, b * d_cap), fill, dtype=np.float32)
    for jj in range(b):
        cnt = int(counts[jj])
        if not cnt:
            continue
        rows = np.asarray(stripe.seg_local[jj, :cnt])
        cols = jj * d_cap + np.asarray(stripe.gat_local[jj, :cnt]).astype(np.int64)
        if stripe.w is not None and semiring != "min_src":
            vals = np.asarray(stripe.w[jj, :cnt], dtype=np.float32)
        else:
            vals = np.ones(cnt, dtype=np.float32)
        fold.at(m, (rows, cols), vals)
    return m


@dataclasses.dataclass(frozen=True)
class DenseRegion:
    """Compacted high-out-degree ("dense", paper §3.5) vector region.

    dense vertices of block k occupy slots [0, d_count[k]) of row k; the
    global compact index of vertex q is psi(q) * d_cap + slot(q).
    """

    gather_idx: Any   # [b, d_cap] int32 — local index of each dense vertex
    d_count: Any      # [b] int32
    d_cap: int
    theta: float


jax.tree_util.register_dataclass(
    DenseRegion,
    data_fields=["gather_idx", "d_count"],
    meta_fields=["d_cap", "theta"],
)


# ---------------------------------------------------------------------------
# Planned packing (planner.ExecutionPlan -> device layouts).
#
# The per-block execution plan splits a worker's stripe into three groups:
#   skip  — structurally empty blocks, dropped entirely at pack time;
#   ell   — sparse blocks packed as ROW-BUCKETED ELL slices: destination rows
#           are grouped by degree into power-of-two buckets, each bucket a
#           slot-major [D_k, R_k] table with its own (much tighter) width, so
#           one skewed row no longer pads every row of the stripe to d_max;
#           R_k is padded to the kernel's lane tiling (ell_rows);
#   dense — near-dense blocks materialized as [n_local, n_local] semiring
#           matrices for the MXU kernel.
# Rows of every table carry their *flat output index* so same-tactic blocks
# across the whole stripe fuse into per-bucket kernel launches whose results
# scatter back into one output vector (placement._planned_* executors).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EllBucket:
    """One degree-bucket ELL slice covering all ell-tactic blocks of a stripe.

    rows: [R] int32 flat output row of each table row (-1 = padding row,
      introduced when stacking workers to a common, lane-aligned R); cols:
      [D, R] int32 slot-major gather index into the flat source vector (-1 =
      padding slot); w: [D, R] matching weights or None.  Every destination
      row lives in exactly ONE bucket (its degree picks it), so bucket
      results scatter with plain ``set`` — no cross-bucket combine.
    """

    rows: Any        # [(b_w,) R] int32; -1 = pad
    cols: Any        # [(b_w,) D, R] int32; -1 = pad
    w: Any | None    # matching weights, or None

    @property
    def d_cap(self) -> int:
        return self.cols.shape[-2]


jax.tree_util.register_dataclass(
    EllBucket, data_fields=["rows", "cols", "w"], meta_fields=[])


@dataclasses.dataclass(frozen=True)
class DenseGroup:
    """The dense-tactic blocks of a stripe, fused for one MXU launch.

    layout='vertical': matrix [k, n_local, n_local] (one per dense block,
      columns = worker-local sources), index [k] = destination block ids
      (-1 = stacking pad, its matrix is identity-filled and dropped at
      scatter time).
    layout='merged': matrix [n_local, k * n_local] (dense source blocks'
      columns concatenated), index [k] = source block ids (stacking pads use
      index 0 — harmless, their columns are identity-filled).
    """

    matrix: Any      # see above
    index: Any       # [(b_w,) k] int32


jax.tree_util.register_dataclass(
    DenseGroup, data_fields=["matrix", "index"], meta_fields=[])


@dataclasses.dataclass(frozen=True)
class PlannedStripe:
    """One worker's plan-packed stripe: bucketed ELL slices + dense group.

    layout='vertical' (vertical / hybrid-sparse stripes): output space is the
    flat partial vector [b * n_local] (block i rows at i * n_local); cols
    index the worker-local source vector [n_local].
    layout='merged' (horizontal stripes): output space is the worker's result
    sub-vector [n_local]; cols are pre-offset to jj * n_local + gat_local,
    indexing the flat all-gathered vector [b * n_local].
    """

    buckets: tuple   # tuple[EllBucket, ...]
    dense: DenseGroup | None
    rows_out: int    # flat output size (b * n_local | n_local)
    layout: str      # 'vertical' | 'merged'


jax.tree_util.register_dataclass(
    PlannedStripe,
    data_fields=["buckets", "dense"],
    meta_fields=["rows_out", "layout"],
)


def pack_bucketed_ell(
    out_rows: np.ndarray,
    cols: np.ndarray,
    w: np.ndarray | None,
    boundaries: tuple[int, ...],
) -> tuple:
    """Flat edge arrays -> row-bucketed ELL slices.

    out_rows[e] is the flat output row of edge e, cols[e] its gather index.
    Each output row with degree d goes to the first bucket whose width
    boundary >= d; bucket k is packed as a [boundaries[k], R_k] table.  All
    len(boundaries) buckets are emitted (possibly with R_k = 0) so the pytree
    structure is identical across workers; stack_planned drops buckets that
    are empty on every worker.
    """
    out_rows = np.asarray(out_rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    bounds = np.asarray(boundaries, dtype=np.int64)
    if out_rows.size:
        deg = np.bincount(out_rows)
        present = np.nonzero(deg)[0]
        assert int(deg.max()) <= int(bounds[-1]), (int(deg.max()), boundaries)
        bucket_of = np.searchsorted(bounds, deg[present], side="left")
        remap = np.full(int(out_rows.max()) + 1, -1, dtype=np.int64)
    else:
        present = np.zeros(0, dtype=np.int64)
        bucket_of = np.zeros(0, dtype=np.int64)
        remap = np.zeros(0, dtype=np.int64)

    has_w = w is not None
    buckets = []
    for k, cap_k in enumerate(boundaries):
        rows_k = present[bucket_of == k]
        if rows_k.size == 0:
            buckets.append(EllBucket(
                rows=np.zeros((0,), np.int32),
                cols=np.full((cap_k, 0), -1, np.int32),
                w=np.zeros((cap_k, 0), np.float32) if has_w else None))
            continue
        remap[:] = -1
        remap[rows_k] = np.arange(rows_k.size)
        sel = remap[out_rows] >= 0
        cols_k, w_k = _pack_ell(
            remap[out_rows[sel]], cols[sel],
            np.asarray(w)[sel] if has_w else None,
            rows_k.size, d_cap=cap_k)
        buckets.append(EllBucket(rows=rows_k.astype(np.int32), cols=cols_k, w=w_k))
    return tuple(buckets)


def materialize_dense_block(
    dst: np.ndarray, src: np.ndarray, w: np.ndarray | None, n_local: int, semiring: str
) -> np.ndarray:
    """One dense-tactic block's edges -> a [n_local, n_local] semiring matrix
    (fill = combineAll identity / presence 0; parallel edges fold)."""
    fill, fold = SEMIRING_FILL_FOLD[semiring]
    m = np.full((n_local, n_local), fill, dtype=np.float32)
    if w is not None and semiring != "min_src":
        vals = np.asarray(w, dtype=np.float32)
    else:
        vals = np.ones(len(dst), dtype=np.float32)
    fold.at(m, (np.asarray(dst), np.asarray(src)), vals)
    return m


def pack_planned_stripe(
    stripe: BlockEdges,
    tactics: tuple[str, ...],
    n_local: int,
    *,
    layout: str,
    boundaries: tuple[int, ...],
    semiring: str,
) -> PlannedStripe:
    """Pack one worker's stripe against its per-block tactics (see module
    section above).  tactics[k] is the tactic of the k-th inner block."""
    assert layout in ("vertical", "merged"), layout
    b = stripe.seg_local.shape[0]
    counts = np.asarray(stripe.count)
    has_w = stripe.w is not None

    out_rows_l: list[np.ndarray] = []
    cols_l: list[np.ndarray] = []
    w_l: list[np.ndarray] = []
    dense_mats: list[np.ndarray] = []
    dense_index: list[int] = []
    for k in range(b):
        cnt = int(counts[k])
        if tactics[k] == "skip" or cnt == 0:
            continue
        seg = np.asarray(stripe.seg_local[k, :cnt], dtype=np.int64)
        gat = np.asarray(stripe.gat_local[k, :cnt], dtype=np.int64)
        wk = np.asarray(stripe.w[k, :cnt]) if has_w else None
        if tactics[k] == "ell":
            if layout == "vertical":
                out_rows_l.append(k * n_local + seg)
                cols_l.append(gat)
            else:
                out_rows_l.append(seg)
                cols_l.append(k * n_local + gat)
            if has_w:
                w_l.append(wk)
        else:  # dense
            dense_mats.append(materialize_dense_block(seg, gat, wk, n_local, semiring))
            dense_index.append(k)

    cat = lambda xs, dt: (np.concatenate(xs) if xs else np.zeros(0, dt))
    buckets = pack_bucketed_ell(
        cat(out_rows_l, np.int64), cat(cols_l, np.int64),
        cat(w_l, np.float32) if has_w else None, boundaries)

    dense = None
    if dense_mats:
        if layout == "vertical":
            dense = DenseGroup(matrix=np.stack(dense_mats),
                               index=np.asarray(dense_index, np.int32))
        else:
            dense = DenseGroup(matrix=np.concatenate(dense_mats, axis=1),
                               index=np.asarray(dense_index, np.int32))
    rows_out = b * n_local if layout == "vertical" else n_local
    return PlannedStripe(buckets=buckets, dense=dense, rows_out=rows_out, layout=layout)


def stack_planned(stripes: list[PlannedStripe], semiring: str) -> PlannedStripe:
    """b per-worker planned stripes -> one stripe with a leading worker axis.

    Buckets share widths (plan-level boundaries) so only the row counts pad
    (rows = -1, cols = -1, to the max over workers at ell_rows alignment);
    buckets empty on EVERY worker are dropped.  Dense
    groups pad to the max dense-block count with identity-filled matrices
    (index -1 for 'vertical' — dropped at scatter; index 0 for 'merged' —
    the identity-filled columns contribute the combineAll identity)."""
    layout = stripes[0].layout
    n_buckets = len(stripes[0].buckets)
    fill, _ = SEMIRING_FILL_FOLD[semiring]

    out_buckets = []
    for k in range(n_buckets):
        bs = [s.buckets[k] for s in stripes]
        r_max = ell_rows(max(x.rows.shape[0] for x in bs))
        if r_max == 0:
            continue
        has_w = bs[0].w is not None
        rows = np.stack([_pad_to(x.rows, r_max, -1) for x in bs])
        cols = np.stack([_pad_rows(x.cols, r_max, -1) for x in bs])
        w = np.stack([_pad_rows(x.w, r_max, 0) for x in bs]) if has_w else None
        out_buckets.append(EllBucket(rows=rows, cols=cols, w=w))

    k_max = max((0 if s.dense is None else s.dense.index.shape[0]) for s in stripes)
    dense = None
    if k_max:
        mats, idxs = [], []
        for s in stripes:
            k_s = 0 if s.dense is None else s.dense.index.shape[0]
            if layout == "vertical":
                nl = s.dense.matrix.shape[-1] if s.dense is not None else _dense_nl(stripes)
                m = (s.dense.matrix if k_s else
                     np.zeros((0, nl, nl), np.float32))
                pad = np.full((k_max - k_s, nl, nl), fill, np.float32)
                mats.append(np.concatenate([m, pad]) if k_max - k_s else m)
                idx = (s.dense.index if k_s else np.zeros(0, np.int32))
                idxs.append(_pad_to(idx, k_max, -1))
            else:
                nl = s.rows_out
                m = (s.dense.matrix if k_s else np.zeros((nl, 0), np.float32))
                pad = np.full((nl, (k_max - k_s) * nl), fill, np.float32)
                mats.append(np.concatenate([m, pad], axis=1) if k_max - k_s else m)
                idx = (s.dense.index if k_s else np.zeros(0, np.int32))
                idxs.append(_pad_to(idx, k_max, 0))
        dense = DenseGroup(matrix=np.stack(mats), index=np.stack(idxs))
    return PlannedStripe(buckets=tuple(out_buckets), dense=dense,
                         rows_out=stripes[0].rows_out, layout=layout)


def pack_streamed_stripe(
    stripe: BlockEdges,
    tactics: tuple[str, ...],
    n_local: int,
    *,
    boundaries: tuple[int, ...],
    semiring: str,
) -> PlannedStripe:
    """Bucketed-ELL slices REGROUPED PER DESTINATION BLOCK for the streamed
    executor (planner.ExecutionPlan.stream='on', the per-destination-block
    launch schedule of ``ExecutionPlan.launch_schedule``).

    Where ``pack_planned_stripe(layout='vertical')`` fuses all ell-tactic
    blocks of a worker's stripe into stripe-wide buckets over the flat
    [b * n_local] output space, this packer keeps a leading destination-block
    axis so ``lax.scan`` can run one block's launches at a time: bucket k is
    rows [b, R_k] (block-LOCAL destination rows, -1 = pad; R_k = the max row
    count of bucket k over the b blocks, at ell_rows alignment) with
    slot-major cols [b, boundaries[k], R_k] (worker-local sources, -1 =
    pad).  Dense-tactic blocks keep the
    'vertical' DenseGroup layout (matrix [k, n_local, n_local], index [k]) —
    they run as per-block MXU launches outside the scan.  rows_out stays
    b * n_local (the flat partial space both schedules feed the exchange
    from), layout='streamed'.
    """
    b = stripe.seg_local.shape[0]
    counts = np.asarray(stripe.count)
    has_w = stripe.w is not None
    empty = np.zeros(0, np.int64)

    per_block: list[tuple] = []
    dense_mats: list[np.ndarray] = []
    dense_index: list[int] = []
    for k in range(b):
        cnt = int(counts[k])
        seg = np.asarray(stripe.seg_local[k, :cnt], dtype=np.int64)
        gat = np.asarray(stripe.gat_local[k, :cnt], dtype=np.int64)
        wk = np.asarray(stripe.w[k, :cnt]) if has_w else None
        if tactics[k] == "dense" and cnt:
            dense_mats.append(materialize_dense_block(seg, gat, wk, n_local, semiring))
            dense_index.append(k)
            seg, gat, wk = empty, empty, (empty.astype(np.float32) if has_w else None)
        elif tactics[k] == "skip" or cnt == 0:
            seg, gat, wk = empty, empty, (empty.astype(np.float32) if has_w else None)
        per_block.append(pack_bucketed_ell(seg, gat, wk, boundaries))

    out_buckets = []
    for kk, cap_k in enumerate(boundaries):
        bs = [pb[kk] for pb in per_block]
        r_max = ell_rows(max(x.rows.shape[0] for x in bs))
        rows = np.stack([_pad_to(x.rows, r_max, -1) for x in bs])
        cols = np.stack([_pad_rows(x.cols, r_max, -1) for x in bs])
        w = np.stack([_pad_rows(x.w, r_max, 0) for x in bs]) if has_w else None
        out_buckets.append(EllBucket(rows=rows, cols=cols, w=w))

    dense = None
    if dense_mats:
        dense = DenseGroup(matrix=np.stack(dense_mats),
                           index=np.asarray(dense_index, np.int32))
    return PlannedStripe(buckets=tuple(out_buckets), dense=dense,
                         rows_out=b * n_local, layout="streamed")


def stack_streamed(
    stripes: list[PlannedStripe], semiring: str, *, worker_axis: int = 0
) -> PlannedStripe:
    """b per-worker streamed stripes -> one stripe with a worker axis.

    worker_axis=0 stacks bucket arrays [b_w, b, D, R] for shard_map (the
    leading axis is what the mesh splits); worker_axis=1 stacks them
    scan-major [b, b_w, D, R] for emulation mode, so the executor's
    ``lax.scan`` over destination blocks slices the leading axis without a
    whole-table transpose temporary.  Buckets pad R to the cross-worker max
    (rows/cols = -1) and are dropped when empty on EVERY (worker, block);
    dense groups stay worker-leading in both modes (the executor unrolls
    them per worker) and pad like ``stack_planned``'s vertical layout."""
    assert worker_axis in (0, 1), worker_axis
    n_buckets = len(stripes[0].buckets)
    fill, _ = SEMIRING_FILL_FOLD[semiring]

    out_buckets = []
    for k in range(n_buckets):
        bs = [s.buckets[k] for s in stripes]
        r_max = ell_rows(max(x.rows.shape[-1] for x in bs))
        if r_max == 0:
            continue
        has_w = bs[0].w is not None
        rows = np.stack([_pad_rows(x.rows, r_max, -1) for x in bs], axis=worker_axis)
        cols = np.stack([_pad_rows(x.cols, r_max, -1) for x in bs], axis=worker_axis)
        w = None
        if has_w:
            w = np.stack([_pad_rows(x.w, r_max, 0) for x in bs], axis=worker_axis)
        out_buckets.append(EllBucket(rows=rows, cols=cols, w=w))

    k_max = max((0 if s.dense is None else s.dense.index.shape[0]) for s in stripes)
    dense = None
    if k_max:
        nl = _dense_nl(stripes)
        mats, idxs = [], []
        for s in stripes:
            k_s = 0 if s.dense is None else s.dense.index.shape[0]
            m = s.dense.matrix if k_s else np.zeros((0, nl, nl), np.float32)
            pad = np.full((k_max - k_s, nl, nl), fill, np.float32)
            mats.append(np.concatenate([m, pad]) if k_max - k_s else m)
            idx = s.dense.index if k_s else np.zeros(0, np.int32)
            idxs.append(_pad_to(idx, k_max, -1))
        dense = DenseGroup(matrix=np.stack(mats), index=np.stack(idxs))
    return PlannedStripe(buckets=tuple(out_buckets), dense=dense,
                         rows_out=stripes[0].rows_out, layout="streamed")


def _pad_rows(a: np.ndarray, r: int, fill) -> np.ndarray:
    """Pad the trailing (row) axis of a bucket array to r entries."""
    widths = [(0, 0)] * (a.ndim - 1) + [(0, r - a.shape[-1])]
    return np.pad(a, widths, constant_values=fill)


def _dense_nl(stripes: list[PlannedStripe]) -> int:
    for s in stripes:
        if s.dense is not None:
            return s.dense.matrix.shape[-1]
    raise AssertionError("no dense group on any worker")


def planned_to_edges(planned: PlannedStripe) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """Bucketed-ELL slices -> flat (out_row, col, w) edge arrays, lexsorted by
    (out_row, col) — the pack/unpack direction of the round-trip property
    test.  Covers the ell-tactic blocks of an UNSTACKED stripe (rows [R])."""
    rows_l, cols_l, w_l = [], [], []
    has_w = any(b.w is not None for b in planned.buckets)
    for b in planned.buckets:
        rows = np.asarray(b.rows)
        cols = np.asarray(b.cols)                    # [D, R]
        rr = np.broadcast_to(rows[None, :], cols.shape)
        valid = (cols >= 0) & (rr >= 0)
        rows_l.append(rr[valid])
        cols_l.append(cols[valid])
        if has_w:
            w_l.append(np.asarray(b.w)[valid])
    out_rows = np.concatenate(rows_l) if rows_l else np.zeros(0, np.int64)
    cols = np.concatenate(cols_l) if cols_l else np.zeros(0, np.int64)
    w = np.concatenate(w_l) if has_w and w_l else None
    order = np.lexsort((cols, out_rows))
    return out_rows[order], cols[order], (w[order] if w is not None else None)
