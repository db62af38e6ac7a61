"""PMV placement strategies (paper §3.2-3.5) as JAX SPMD programs.

Each placement is written as a *per-worker* function; communication goes
through the tiny helpers below that lower to `jax.lax` collectives when an
``axis_name`` is given (inside shard_map), and to pure jnp reshapes over an
explicit leading worker axis when it is None ("emulation mode": single-device
execution of all b workers, used by CPU tests/benchmarks — bitwise the same
math as the SPMD path).

Mapping to the paper:
- PMV_horizontal (Alg. 1): ``all_gather(v)`` replaces "each worker loads all
  vector blocks from distributed storage"; the output sub-vector is written
  once (stays sharded).
- PMV_vertical   (Alg. 2): local column-stripe sub-multiplications produce
  partial vectors v^(i,j); the HDFS store/load of partials becomes an
  ``all_to_all``, either dense ([b, n_local]) or *compacted sparse*
  (indices+values up to the structural capacity — the TPU analog of shuffling
  only non-empty entries, see sparse_exchange.py).
- PMV_hybrid     (Alg. 4): sparse region runs vertical with the compact
  exchange; the dense region's sub-vector v_d is small by construction
  (high-out-degree vertices only), so it is all-gathered (horizontal).

Execution modes (planner.ExecutionPlan.mode, forced via StepConfig.backend):
- 'xla' (default): the generic gather + segment-combine lowering below.
- 'pallas': per-worker block compute runs the validated Pallas kernels —
  sparse stripes through the ELL semiring kernel (kernels/ell_spmv, packed
  at pre-partition time, blocks.stripe_to_ell), the hybrid dense region
  through the MXU/VPU dense kernel (kernels/block_gimv) on the materialized
  [n_local, b*d_cap] matrix.  Collectives, compaction and assign are shared
  with the xla path, so both backends are interchangeable per step.
- 'planned' (backend='auto'): per-BLOCK tactics from the density-driven
  ExecutionPlan (core/planner.py).  The _planned_* executors below group
  same-tactic blocks into fused launches: skip blocks were dropped at pack
  time, ell blocks run per degree-bucket ELL kernel calls over row-bucketed
  slices (blocks.PlannedStripe), dense blocks run one fused MXU semiring
  matmul; bucket/dense results scatter into one flat output vector (each
  destination row lives in exactly one group, so plain ``set`` suffices).
  The plan's ``scatter`` field additionally picks the receive side of the
  sparse exchange: the XLA segment op or the Pallas scatter-combine kernel.
  With ``plan.stream='on'`` the vertical/hybrid compact path trades the
  fused launches for ``_streamed_planned_compact``: a ``lax.scan`` over
  destination blocks that compacts each [n_local] partial into its fixed
  [cap] exchange slot as it is produced (paper Alg. 2's schedule), keeping
  live memory at O(n_local + b*cap) instead of O(b*n_local).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import sparse_exchange
from repro.core.blocks import BlockEdges, DenseRegion, EllStripe, PlannedStripe
from repro.core.gimv import (GimvSpec, combine2, combine_elementwise,
                             segment_combine, tree_combine)
from repro.exchange import runtime as packed_rt
from repro.kernels.block_gimv import dense_gimv, dense_gimv_multi, semiring_of
from repro.kernels.ell_spmv import ell_gimv, ell_gimv_multi

__all__ = [
    "horizontal_step",
    "vertical_step",
    "hybrid_step",
    "block_gimv_partials",
    "gathered_gimv",
    "ell_gimv_call",
    "single_block_compact",
    "single_block_partial",
    "single_block_contrib",
    "apply_assign",
]


# --------------------------------------------------------------------------
# Communication helpers: axis_name=None => emulation over leading worker axis.
# --------------------------------------------------------------------------

def _all_gather(x, axis_name):
    """Per-worker [.] -> [b, .] (tiled on every worker)."""
    if axis_name is None:
        b = x.shape[0]
        return jnp.broadcast_to(x[None], (b,) + x.shape)  # [b_worker, b, ...]
    return lax.all_gather(x, axis_name)


def _all_to_all(x, axis_name):
    """Per-worker [b, .] -> [b, .] transposed across workers."""
    if axis_name is None:
        return jnp.swapaxes(x, 0, 1)  # [b_worker, b_slice, ...] transpose
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)


# --------------------------------------------------------------------------
# Per-worker block compute (shared by every placement).
# --------------------------------------------------------------------------

def _edges_x(spec: GimvSpec, stripe: BlockEdges, v_gathered_rows: jnp.ndarray) -> jnp.ndarray:
    """combine2 over all edges of a stripe.

    v_gathered_rows: [b, m] — row k is the vector the k-th inner block's
    gat_local indexes into (v^(j) broadcast for vertical; v_all for
    horizontal).  Returns x: [b, E_cap] with padding set to the identity.

    A trailing query axis ([b, m, Q]) broadcasts the per-edge weights and the
    padding mask across queries and returns x: [b, E_cap, Q].
    """
    b, e_cap = stripe.seg_local.shape
    mask = jnp.arange(e_cap, dtype=jnp.int32)[None, :] < stripe.count[:, None]
    if v_gathered_rows.ndim == 3:  # multi-query
        vj = jnp.take_along_axis(v_gathered_rows, stripe.gat_local[:, :, None], axis=1)
        w = None if stripe.w is None else stripe.w[:, :, None]
        mask = mask[:, :, None]
    else:
        vj = jnp.take_along_axis(v_gathered_rows, stripe.gat_local, axis=1)
        w = stripe.w
    if spec.needs_weights:
        x = combine2(spec, w, vj)
    else:
        x = combine2(spec, None, vj)
    return jnp.where(mask, x, jnp.asarray(spec.identity, x.dtype))


def block_gimv_partials(spec: GimvSpec, stripe: BlockEdges, v_local: jnp.ndarray, n_local: int) -> jnp.ndarray:
    """Vertical sub-multiplications: v^(i,j) = M^(i,j) (x) v^(j) for all i.

    Returns partials [b, n_local] (identity where structurally empty); with a
    trailing query axis on v_local ([n_local, Q]) returns [b, n_local, Q].
    """
    b = stripe.seg_local.shape[0]
    v_rows = jnp.broadcast_to(v_local[None], (b,) + v_local.shape)
    x = _edges_x(spec, stripe, v_rows)
    seg = stripe.seg_local + (jnp.arange(b, dtype=jnp.int32) * n_local)[:, None]
    e_cap = stripe.seg_local.shape[1]
    if x.ndim == 3:
        flat = segment_combine(spec, x.reshape(b * e_cap, -1), seg.reshape(-1), b * n_local)
        return flat.reshape(b, n_local, x.shape[-1])
    flat = segment_combine(spec, x.reshape(-1), seg.reshape(-1), b * n_local)
    return flat.reshape(b, n_local)


def _single_block_x(spec: GimvSpec, seg, gat, w, cnt, v_rows, batched: bool):
    """combine2 + padding mask for ONE block's edge arrays ([E_cap])."""
    ident = jnp.asarray(spec.identity, spec.dtype)
    e_cap = seg.shape[0]
    vj = v_rows[gat]
    if batched:
        w = None if w is None else w[:, None]
    if spec.needs_weights:
        x = combine2(spec, w, vj)
    else:
        x = combine2(spec, None, vj)
    mask = jnp.arange(e_cap, dtype=jnp.int32) < cnt
    return jnp.where(mask[:, None] if batched else mask, x, ident)


def single_block_partial(spec: GimvSpec, seg, gat, w, cnt, v_local,
                         n_local: int):
    """One destination block's vertical sub-multiplication: seg/gat/w [E_cap]
    edge arrays against the worker-local vector v_local [n_local(, Q)] ->
    the dense partial [n_local(, Q)].  Shared by the value-compacting path
    (``single_block_compact``) and the packed-exchange path (which gathers
    the partial at its static index set instead of compacting)."""
    batched = v_local.ndim == 2
    x = _single_block_x(spec, seg, gat, w, cnt, v_local, batched)
    return segment_combine(spec, x, seg, n_local)


def single_block_compact(spec: GimvSpec, seg, gat, w, cnt, v_local,
                         n_local: int, capacity: int):
    """One destination block's vertical sub-multiplication + immediate
    compaction: seg/gat/w [E_cap] edge arrays against the worker-local
    vector v_local [n_local(, Q)] -> (idx [cap], val [cap(, Q)], overflow,
    logical).  This is the per-step body of the Alg. 2 streaming scan below
    — shared verbatim with the disk-residency executor (repro.store), which
    fetches each block's shard slice from disk and must stay bitwise
    identical to the resident path."""
    partial = single_block_partial(spec, seg, gat, w, cnt, v_local, n_local)
    return sparse_exchange.compact_partials(
        spec, partial, capacity, None, batched=v_local.ndim == 2)


def single_block_contrib(spec: GimvSpec, seg, gat, w, cnt, v_src, n_local: int):
    """One source block's horizontal contribution: combine2 over the block's
    edges against the SOURCE block's vector v_src [n_local(, Q)], segment-
    combined into the destination rows [n_local(, Q)].  The disk-residency
    horizontal executor streams these per source block and folds them with
    combineAll — the ROADMAP 'stream the horizontal gather' schedule."""
    batched = v_src.ndim == 2
    x = _single_block_x(spec, seg, gat, w, cnt, v_src, batched)
    return segment_combine(spec, x, seg, n_local)


def block_gimv_partials_compact(
    spec: GimvSpec, stripe: BlockEdges, v_local: jnp.ndarray, n_local: int, capacity: int
):
    """Streamed vertical sub-multiplications with immediate compaction.

    The paper's Alg. 2 stores each v^(i,j) to distributed storage as it is
    produced (never holding all b partials); the TPU analog scans over
    destination blocks i, compacting each [n_local] partial to (idx, val)
    pairs of static `capacity` before moving on.  Peak live memory is
    O(n_local + b*capacity) instead of O(b * n_local) — the difference
    between fitting and OOM at ClueWeb12 scale (b * n_local = |v| = 25 GB).

    Returns (idx [b, cap], val [b, cap], overflow_rows, logical_elems); with
    a trailing query axis on v_local ([n_local, Q]) val becomes [b, cap, Q]
    sharing one index set per partial row (wire format (idx, val[Q])).
    """

    def body(_, blk):
        seg, gat, w, cnt = blk
        idx, val, over, logical = single_block_compact(
            spec, seg, gat, w, cnt, v_local, n_local, capacity)
        return None, (idx, val, over, logical)

    xs = (stripe.seg_local, stripe.gat_local,
          stripe.w if stripe.w is not None else jnp.zeros_like(stripe.seg_local),
          stripe.count)
    _, (idx, val, over, logical) = jax.lax.scan(body, None, xs)
    return idx, val, jnp.sum(over), jnp.sum(logical)


def block_gimv_partials_payload(
    spec: GimvSpec, stripe: BlockEdges, v_local: jnp.ndarray,
    send_rows: jnp.ndarray, n_local: int
):
    """Streamed vertical sub-multiplications gathered at the static packed
    order (the paper's schedule, with the packed exchange's structure-free
    payload instead of (idx, val) compaction).  ``send_rows`` [b, p] is the
    prepare()-time gather order per destination block; the scan keeps live
    memory at O(n_local + b*p).  Returns (payload [b, p(, Q)], logical)."""

    def body(_, blk):
        seg, gat, w, cnt, srows = blk
        partial_ = single_block_partial(spec, seg, gat, w, cnt, v_local, n_local)
        pay = packed_rt.gather_payload(spec, partial_, srows)
        return None, (pay, sparse_exchange.count_non_identity(spec, pay))

    xs = (stripe.seg_local, stripe.gat_local,
          stripe.w if stripe.w is not None else jnp.zeros_like(stripe.seg_local),
          stripe.count, send_rows)
    _, (val, logical) = jax.lax.scan(body, None, xs)
    return val, jnp.sum(logical)


def gathered_gimv(spec: GimvSpec, stripe: BlockEdges, v_all: jnp.ndarray, n_local: int) -> jnp.ndarray:
    """Horizontal compute: r^(i) = combineAll_j M^(i,j) (x) v^(j) with the
    whole vector v_all [b, n_local] available locally.  A trailing query axis
    ([b, n_local, Q]) is carried through to r [n_local, Q]."""
    b, e_cap = stripe.seg_local.shape
    x = _edges_x(spec, stripe, v_all)
    seg = stripe.seg_local + (jnp.arange(b, dtype=jnp.int32) * n_local)[:, None]
    if x.ndim == 3:
        flat = segment_combine(spec, x.reshape(b * e_cap, -1), seg.reshape(-1), b * n_local)
        contribs = flat.reshape(b, n_local, x.shape[-1])
    else:
        flat = segment_combine(spec, x.reshape(-1), seg.reshape(-1), b * n_local)
        contribs = flat.reshape(b, n_local)
    # combineAll across source blocks: a pairwise-tree fold whose association
    # order depends only on b, so the streamed disk executor folding the same
    # per-block contributions (in any launch order) is bitwise identical —
    # including float sum (plus_times).
    return tree_combine(spec, [contribs[j] for j in range(b)])


# --------------------------------------------------------------------------
# Pallas-backend per-worker compute (backend='pallas').  The collectives,
# compaction and assign stay shared with the xla path above.
# --------------------------------------------------------------------------

def ell_gimv_call(spec: GimvSpec, cols, w, v, interpret: bool):
    """Dispatch slot-major ELL tables to the (multi-)query semiring kernel.

    cols/w: [*L, D, R] (leading axes batch tables into one launch); v: [N]
    or [N, Q] -> r: [*L, R] or [*L, R, Q]."""
    semiring = semiring_of(spec.combine2, spec.combine_all)
    if not spec.needs_weights:
        w = None
    if v.ndim == 2:
        return ell_gimv_multi(cols, w, v, semiring=semiring, interpret=interpret)
    return ell_gimv(cols, w, v, semiring=semiring, interpret=interpret)


def _ell_gathered_gimv(spec: GimvSpec, ell: EllStripe, v_local, n_local: int,
                       axis_name, interpret: bool):
    """Pallas analog of the horizontal compute: one merged ELL table per
    worker (cols pre-offset into the flat gathered vector), one kernel call.

    Emulation mode batches the workers' tables into one launch — the merged
    cols already index the flat blocked vector, which IS
    v_local.reshape(b * n_local).  Returns r [n_local(, Q)] (emulation:
    [b, n_local(, Q)])."""
    if axis_name is None:
        b = v_local.shape[0]
        v_flat = v_local.reshape((b * n_local,) + v_local.shape[2:])
        return ell_gimv_call(spec, ell.cols, ell.w, v_flat, interpret)
    v_all = _all_gather(v_local, axis_name)          # [b, n_local(, Q)]
    v_flat = v_all.reshape((-1,) + v_all.shape[2:])  # [b*n_local(, Q)]
    return ell_gimv_call(spec, ell.cols, ell.w, v_flat, interpret)


def _ell_block_partials(spec: GimvSpec, ell: EllStripe, v_local, n_local: int,
                        axis_name, interpret: bool):
    """Pallas analog of block_gimv_partials: all b destination-block partials
    in one flattened kernel call.  Emulation folds the worker axis in by
    offsetting cols into the flat per-worker vector.  Returns partials
    [b, n_local(, Q)] (emulation: [b_worker, b, n_local(, Q)])."""
    if axis_name is None:
        b_w = ell.cols.shape[0]
        off = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None, None, None]
        cols = jnp.where(ell.cols >= 0, ell.cols + off, -1)
        v_flat = v_local.reshape((b_w * n_local,) + v_local.shape[2:])
        return ell_gimv_call(spec, cols, ell.w, v_flat, interpret)
    return ell_gimv_call(spec, ell.cols, ell.w, v_local, interpret)


def _ell_partials_compact(spec: GimvSpec, ell: EllStripe, v_local, n_local: int,
                          capacity: int, axis_name, interpret: bool):
    """Pallas analog of block_gimv_partials_compact: scan destination blocks,
    ELL kernel per block, immediate compaction — same O(n_local + b*cap) live
    memory as the xla streaming path.  Handles the emulation worker axis
    internally (cols offset into the flat vector), so callers never vmap a
    pallas_call.  Returns (idx, val, overflow, logical_elems)."""
    emulation = axis_name is None
    batched = v_local.ndim == (3 if emulation else 2)
    if emulation:
        b_w = ell.cols.shape[0]
        off = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None, None]
        v_flat = v_local.reshape((b_w * n_local,) + v_local.shape[2:])
        cols_s = jnp.swapaxes(ell.cols, 0, 1)    # [b, b_w, D, n_local]
        w_s = None if ell.w is None else jnp.swapaxes(ell.w, 0, 1)

        def body(_, blk):
            cols, w = blk                        # [b_w, D, n_local]
            cols = jnp.where(cols >= 0, cols + off, -1)
            partial_ = ell_gimv_call(spec, cols, w, v_flat, interpret)
            return None, sparse_exchange.compact_partials(
                spec, partial_, capacity, None, batched=batched)

        _, (idx, val, over, logical) = lax.scan(body, None, (cols_s, w_s))
        idx = jnp.swapaxes(idx, 0, 1)            # -> [b_w, b, cap]
        val = jnp.swapaxes(val, 0, 1)
        return idx, val, jnp.sum(over), jnp.sum(logical)

    def body(_, blk):
        cols, w = blk                            # [D, n_local]
        r = ell_gimv_call(spec, cols, w, v_local, interpret)
        return None, sparse_exchange.compact_partials(
            spec, r, capacity, None, batched=batched)

    _, (idx, val, over, logical) = lax.scan(body, None, (ell.cols, ell.w))
    return idx, val, jnp.sum(over), jnp.sum(logical)


def _ell_partials_payload(spec: GimvSpec, ell: EllStripe, v_local, n_local: int,
                          send_rows, axis_name, interpret: bool):
    """Pallas analog of block_gimv_partials_payload: scan destination blocks,
    ELL kernel per block, immediate gather at the static packed send order.
    Returns (payload, logical) — payload [b, p(, Q)] per worker (emulation:
    [b_w, b, p(, Q)])."""
    emulation = axis_name is None
    if emulation:
        b_w = ell.cols.shape[0]
        off = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None, None]
        v_flat = v_local.reshape((b_w * n_local,) + v_local.shape[2:])
        cols_s = jnp.swapaxes(ell.cols, 0, 1)    # [b, b_w, D, n_local]
        w_s = None if ell.w is None else jnp.swapaxes(ell.w, 0, 1)
        srows_s = jnp.swapaxes(send_rows, 0, 1)  # [b, b_w, p]

        def body(_, blk):
            cols, w, srows = blk                 # [b_w, D, n_local] / [b_w, p]
            cols = jnp.where(cols >= 0, cols + off, -1)
            partial_ = ell_gimv_call(spec, cols, w, v_flat, interpret)
            pay = packed_rt.gather_payload(spec, partial_, srows)
            return None, (pay, sparse_exchange.count_non_identity(spec, pay))

        _, (val, logical) = lax.scan(body, None, (cols_s, w_s, srows_s))
        return jnp.swapaxes(val, 0, 1), jnp.sum(logical)

    def body(_, blk):
        cols, w, srows = blk                     # [D, n_local] / [p]
        r = ell_gimv_call(spec, cols, w, v_local, interpret)
        pay = packed_rt.gather_payload(spec, r, srows)
        return None, (pay, sparse_exchange.count_non_identity(spec, pay))

    _, (val, logical) = lax.scan(body, None, (ell.cols, ell.w, send_rows))
    return val, jnp.sum(logical)


def _dense_region_gimv(spec: GimvSpec, dense_matrix, v_d, n_local: int,
                       axis_name, interpret: bool):
    """Pallas dense-region compute: the materialized [n_local, b*d_cap]
    matrix against the flat gathered dense sub-vector, on the MXU
    (plus_times) / VPU (tropical) kernels.  v_d: per-worker [b, d_cap(, Q)]
    in emulation (the full blocked dense vector), [d_cap(, Q)] in SPMD
    (all-gathered inside).  Returns r_dense [n_local(, Q)] (emulation:
    [b_worker, n_local(, Q)])."""
    semiring = semiring_of(spec.combine2, spec.combine_all)
    if axis_name is None:
        b_w = dense_matrix.shape[0]
        k = dense_matrix.shape[-1]
        dm2 = dense_matrix.reshape(b_w * n_local, k)
        v_flat = v_d.reshape((k,) + v_d.shape[2:])
        if v_flat.ndim == 2:
            r = dense_gimv_multi(dm2, v_flat, semiring=semiring, interpret=interpret)
        else:
            r = dense_gimv(dm2, v_flat, semiring=semiring, interpret=interpret)
        return r.reshape((b_w, n_local) + r.shape[1:])
    v_d_all = _all_gather(v_d, axis_name)            # [b, d_cap(, Q)]
    v_flat = v_d_all.reshape((-1,) + v_d_all.shape[2:])
    if v_flat.ndim == 2:
        return dense_gimv_multi(dense_matrix, v_flat, semiring=semiring, interpret=interpret)
    return dense_gimv(dense_matrix, v_flat, semiring=semiring, interpret=interpret)


# --------------------------------------------------------------------------
# Planned executors (mode='planned'): run an ExecutionPlan's per-block
# tactics, grouping same-tactic blocks into fused kernel launches.
# --------------------------------------------------------------------------

def _scatter_set(out, rows, vals, drop):
    """out[rows] = vals, with rows == -1 (stacking pads) routed to the drop
    slot the caller slices off.  Rows are unique across all of a stripe's
    buckets and dense blocks (a destination row lives in exactly one group),
    so a plain ``set`` is the correct combine."""
    safe = jnp.where(rows >= 0, rows, drop)
    return out.at[safe].set(vals, mode="drop")


def _planned_dense_call(spec: GimvSpec, matrix2d, operand, interpret: bool):
    """One fused MXU/VPU launch over a dense group's materialized matrix."""
    semiring = semiring_of(spec.combine2, spec.combine_all)
    if operand.ndim == 2:
        return dense_gimv_multi(matrix2d, operand, semiring=semiring, interpret=interpret)
    return dense_gimv(matrix2d, operand, semiring=semiring, interpret=interpret)


def _planned_merged_gimv(spec: GimvSpec, planned: PlannedStripe, v_local,
                         n_local: int, axis_name, interpret: bool):
    """Planned horizontal compute: per-bucket ELL launches + one dense-group
    matmul against the flat all-gathered vector, scattered/combined into
    r [n_local(, Q)] (emulation: [b_w, n_local(, Q)]).

    Emulation folds the worker axis into the scatter space; the merged cols
    already index the flat blocked vector (= the gathered vector every
    worker holds), so only output rows need per-worker offsets.  The dense
    group runs per worker (each worker gathers a different column slice) —
    in SPMD, where it matters, it is one launch per worker either way."""
    ident = jnp.asarray(spec.identity, spec.dtype)
    if axis_name is None:
        b_w = v_local.shape[0]
        tail = v_local.shape[2:]
        v_flat = v_local.reshape((b_w * n_local,) + tail)
        drop = b_w * n_local
        out = jnp.full((drop + 1,) + tail, ident, spec.dtype)
        woff = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None]
        for bucket in planned.buckets:
            rows = jnp.where(bucket.rows >= 0, bucket.rows + woff, -1).reshape(-1)
            r = ell_gimv_call(spec, bucket.cols, bucket.w, v_flat, interpret)
            out = _scatter_set(out, rows, r.reshape((-1,) + tail), drop)
        r_all = out[:drop].reshape((b_w, n_local) + tail)
        if planned.dense is not None:
            k = planned.dense.index.shape[-1]
            r_ds = []
            for wk in range(b_w):
                operand = v_local[planned.dense.index[wk]].reshape((k * n_local,) + tail)
                r_ds.append(_planned_dense_call(
                    spec, planned.dense.matrix[wk], operand, interpret))
            r_all = combine_elementwise(spec, r_all, jnp.stack(r_ds))
        return r_all
    v_all = _all_gather(v_local, axis_name)          # [b, n_local(, Q)]
    tail = v_all.shape[2:]
    v_flat = v_all.reshape((-1,) + tail)
    out = jnp.full((n_local + 1,) + tail, ident, spec.dtype)
    for bucket in planned.buckets:
        r = ell_gimv_call(spec, bucket.cols, bucket.w, v_flat, interpret)
        out = _scatter_set(out, bucket.rows, r, n_local)
    r_all = out[:n_local]
    if planned.dense is not None:
        k = planned.dense.index.shape[-1]
        operand = v_all[planned.dense.index].reshape((k * n_local,) + tail)
        r_dense = _planned_dense_call(spec, planned.dense.matrix, operand, interpret)
        r_all = combine_elementwise(spec, r_all, r_dense)
    return r_all


def _planned_vertical_partials(spec: GimvSpec, planned: PlannedStripe, v_local,
                               n_local: int, axis_name, interpret: bool):
    """Planned vertical compute: all destination-block partials via per-bucket
    ELL launches + one fused dense-group matmul, scattered into the flat
    partial space [b * n_local].  Returns partials [b, n_local(, Q)]
    (emulation: [b_w, b, n_local(, Q)])."""
    ident = jnp.asarray(spec.identity, spec.dtype)
    b = planned.rows_out // n_local
    if axis_name is None:
        b_w = v_local.shape[0]
        tail = v_local.shape[2:]
        v_flat = v_local.reshape((b_w * n_local,) + tail)
        drop = b_w * planned.rows_out
        out = jnp.full((drop + 1,) + tail, ident, spec.dtype)
        coff = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None, None]
        roff = (jnp.arange(b_w, dtype=jnp.int32) * planned.rows_out)[:, None]
        for bucket in planned.buckets:
            cols = jnp.where(bucket.cols >= 0, bucket.cols + coff, -1)
            rows = jnp.where(bucket.rows >= 0, bucket.rows + roff, -1).reshape(-1)
            r = ell_gimv_call(spec, cols, bucket.w, v_flat, interpret)
            out = _scatter_set(out, rows, r.reshape((-1,) + tail), drop)
        if planned.dense is not None:
            k = planned.dense.index.shape[-1]
            ar = jnp.arange(n_local, dtype=jnp.int32)[None, :]
            for wk in range(b_w):
                m2 = planned.dense.matrix[wk].reshape(k * n_local, n_local)
                r_d = _planned_dense_call(spec, m2, v_local[wk], interpret)
                dix = planned.dense.index[wk][:, None]
                rows_d = jnp.where(
                    dix >= 0, wk * planned.rows_out + dix * n_local + ar, -1
                ).reshape(-1)
                out = _scatter_set(out, rows_d, r_d, drop)
        return out[:drop].reshape((b_w, b, n_local) + tail)
    tail = v_local.shape[1:]
    drop = planned.rows_out
    out = jnp.full((drop + 1,) + tail, ident, spec.dtype)
    for bucket in planned.buckets:
        r = ell_gimv_call(spec, bucket.cols, bucket.w, v_local, interpret)
        out = _scatter_set(out, bucket.rows, r, drop)
    if planned.dense is not None:
        k = planned.dense.index.shape[-1]
        m2 = planned.dense.matrix.reshape(k * n_local, n_local)
        r_d = _planned_dense_call(spec, m2, v_local, interpret)
        ar = jnp.arange(n_local, dtype=jnp.int32)[None, :]
        rows_d = jnp.where(
            planned.dense.index[:, None] >= 0,
            planned.dense.index[:, None] * n_local + ar, -1).reshape(-1)
        out = _scatter_set(out, rows_d, r_d, drop)
    return out[:drop].reshape((b, n_local) + tail)


def _streamed_planned_compact(spec: GimvSpec, streamed: PlannedStripe, v_local,
                              n_local: int, capacity: int, axis_name,
                              interpret: bool):
    """Bucket-streamed planned vertical compute (plan.stream='on').

    The fused ``_planned_vertical_partials`` materializes all b
    destination-block partials ([b, n_local(, Q)] live) before compaction;
    this executor restores the paper Alg. 2's store-as-produced schedule:
    ``lax.scan`` over destination blocks runs each block's bucketed-ELL
    launches (``blocks.pack_streamed_stripe``'s per-block slices — the
    plan's ``launch_schedule``), then immediately
    ``sparse_exchange.compact_chunk``s the [n_local(, Q)] partial into its
    fixed [cap] exchange slot, so live memory is O(n_local + b*cap) instead
    of O(b * n_local).  Dense-tactic blocks run as per-block MXU launches
    after the scan and overwrite their (tactic-exclusive, hence disjoint)
    compact rows.  Handles the emulation worker axis internally (the
    streamed pack is scan-major there, so no transpose temp); returns
    (idx, val, overflow, logical) exactly like the fused path + compaction.
    """
    ident = jnp.asarray(spec.identity, spec.dtype)
    emulation = axis_name is None
    batched = v_local.ndim == (3 if emulation else 2)
    b = streamed.rows_out // n_local

    def bucket_xs():
        # pytree of per-bucket arrays; scan slices the leading (block) axis.
        return tuple((bk.rows, bk.cols, bk.w) for bk in streamed.buckets)

    if emulation:
        b_w = v_local.shape[0]
        tail = v_local.shape[2:]
        v_flat = v_local.reshape((b_w * n_local,) + tail)
        coff = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None, None]
        roff = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None]
        drop = b_w * n_local

        def body(_, bks):
            out = jnp.full((drop + 1,) + tail, ident, spec.dtype)
            for rows, cols, w in bks:            # [b_w(, D), R] per bucket
                cols2 = jnp.where(cols >= 0, cols + coff, -1)
                rows2 = jnp.where(rows >= 0, rows + roff, -1).reshape(-1)
                r = ell_gimv_call(spec, cols2, w, v_flat, interpret)
                out = _scatter_set(out, rows2, r.reshape((-1,) + tail), drop)
            partial_ = out[:drop].reshape((b_w, n_local) + tail)
            return None, sparse_exchange.compact_chunk(
                spec, partial_, capacity, batched=batched)

        _, (idx, val, over, logical) = lax.scan(body, None, bucket_xs(), length=b)
        idx = jnp.swapaxes(idx, 0, 1)            # [b, b_w, cap] -> [b_w, b, cap]
        val = jnp.swapaxes(val, 0, 1)
        over, logical = jnp.sum(over), jnp.sum(logical)
        if streamed.dense is not None:
            for wk in range(b_w):
                for t in range(streamed.dense.index.shape[-1]):
                    r_d = _planned_dense_call(
                        spec, streamed.dense.matrix[wk, t], v_local[wk], interpret)
                    idx_d, val_d, ov_d, lg_d = sparse_exchange.compact_chunk(
                        spec, r_d, capacity, batched=batched)
                    i = streamed.dense.index[wk, t]
                    safe_i = jnp.where(i >= 0, i, b)   # -1 stacking pads drop
                    idx = idx.at[wk, safe_i].set(idx_d, mode="drop")
                    val = val.at[wk, safe_i].set(val_d, mode="drop")
                    over, logical = over + ov_d, logical + lg_d
        return idx, val, over, logical

    def body(_, bks):
        out = jnp.full((n_local + 1,) + v_local.shape[1:], ident, spec.dtype)
        for rows, cols, w in bks:                # [(D,) R] per bucket
            r = ell_gimv_call(spec, cols, w, v_local, interpret)
            out = _scatter_set(out, rows, r, n_local)
        return None, sparse_exchange.compact_chunk(
            spec, out[:n_local], capacity, batched=batched)

    _, (idx, val, over, logical) = lax.scan(body, None, bucket_xs(), length=b)
    over, logical = jnp.sum(over), jnp.sum(logical)
    if streamed.dense is not None:
        for t in range(streamed.dense.index.shape[-1]):
            r_d = _planned_dense_call(spec, streamed.dense.matrix[t], v_local, interpret)
            idx_d, val_d, ov_d, lg_d = sparse_exchange.compact_chunk(
                spec, r_d, capacity, batched=batched)
            i = streamed.dense.index[t]
            safe_i = jnp.where(i >= 0, i, b)
            idx = idx.at[safe_i].set(idx_d, mode="drop")
            val = val.at[safe_i].set(val_d, mode="drop")
            over, logical = over + ov_d, logical + lg_d
    return idx, val, over, logical


def _streamed_planned_payload(spec: GimvSpec, streamed: PlannedStripe, v_local,
                              n_local: int, send_rows, axis_name,
                              interpret: bool):
    """Bucket-streamed planned vertical compute feeding the packed exchange:
    the scan of ``_streamed_planned_compact`` with each destination block's
    [n_local(, Q)] partial gathered at its static send order instead of
    value-compacted.  Dense-tactic blocks run after the scan and overwrite
    their (tactic-exclusive) payload rows — the gather order for a block is
    the same whichever tactic produced its partial.  Returns
    (payload, logical)."""
    ident = jnp.asarray(spec.identity, spec.dtype)
    emulation = axis_name is None
    b = streamed.rows_out // n_local

    def bucket_xs():
        return tuple((bk.rows, bk.cols, bk.w) for bk in streamed.buckets)

    if emulation:
        b_w = v_local.shape[0]
        tail = v_local.shape[2:]
        v_flat = v_local.reshape((b_w * n_local,) + tail)
        coff = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None, None]
        roff = (jnp.arange(b_w, dtype=jnp.int32) * n_local)[:, None]
        drop = b_w * n_local
        srows_s = jnp.swapaxes(send_rows, 0, 1)  # [b, b_w, p]

        def body(_, xs_):
            bks, srows = xs_
            out = jnp.full((drop + 1,) + tail, ident, spec.dtype)
            for rows, cols, w in bks:            # [b_w(, D), R] per bucket
                cols2 = jnp.where(cols >= 0, cols + coff, -1)
                rows2 = jnp.where(rows >= 0, rows + roff, -1).reshape(-1)
                r = ell_gimv_call(spec, cols2, w, v_flat, interpret)
                out = _scatter_set(out, rows2, r.reshape((-1,) + tail), drop)
            partial_ = out[:drop].reshape((b_w, n_local) + tail)
            pay = packed_rt.gather_payload(spec, partial_, srows)
            return None, (pay, sparse_exchange.count_non_identity(spec, pay))

        _, (val, logical) = lax.scan(body, None, (bucket_xs(), srows_s), length=b)
        val = jnp.swapaxes(val, 0, 1)            # [b, b_w, p(, Q)] -> [b_w, b, ...]
        logical = jnp.sum(logical)
        if streamed.dense is not None:
            for wk in range(b_w):
                for t in range(streamed.dense.index.shape[-1]):
                    r_d = _planned_dense_call(
                        spec, streamed.dense.matrix[wk, t], v_local[wk], interpret)
                    i = streamed.dense.index[wk, t]
                    srows_d = send_rows[wk][jnp.where(i >= 0, i, 0)]
                    pay_d = packed_rt.gather_payload(spec, r_d, srows_d)
                    safe_i = jnp.where(i >= 0, i, b)   # -1 stacking pads drop
                    # replace the scan's identity payload for this block, then
                    # correct the count (scan contributed 0 for it).
                    val = val.at[wk, safe_i].set(pay_d, mode="drop")
                    logical = logical + jnp.where(
                        i >= 0, sparse_exchange.count_non_identity(spec, pay_d), 0.0)
        return val, logical

    def body(_, xs_):
        bks, srows = xs_
        out = jnp.full((n_local + 1,) + v_local.shape[1:], ident, spec.dtype)
        for rows, cols, w in bks:                # [(D,) R] per bucket
            r = ell_gimv_call(spec, cols, w, v_local, interpret)
            out = _scatter_set(out, rows, r, n_local)
        pay = packed_rt.gather_payload(spec, out[:n_local], srows)
        return None, (pay, sparse_exchange.count_non_identity(spec, pay))

    _, (val, logical) = lax.scan(body, None, (bucket_xs(), send_rows), length=b)
    logical = jnp.sum(logical)
    if streamed.dense is not None:
        for t in range(streamed.dense.index.shape[-1]):
            r_d = _planned_dense_call(spec, streamed.dense.matrix[t], v_local, interpret)
            i = streamed.dense.index[t]
            srows_d = send_rows[jnp.where(i >= 0, i, 0)]
            pay_d = packed_rt.gather_payload(spec, r_d, srows_d)
            safe_i = jnp.where(i >= 0, i, b)
            val = val.at[safe_i].set(pay_d, mode="drop")
            logical = logical + jnp.where(
                i >= 0, sparse_exchange.count_non_identity(spec, pay_d), 0.0)
    return val, logical


def _packed_payload(spec: GimvSpec, v_local, n_local: int, send_rows, *,
                    stripe=None, ell=None, planned=None, streamed=None,
                    use_planned: bool, use_pallas: bool, axis_name,
                    interpret: bool):
    """Vertical partials through whichever backend, gathered at the packed
    send order.  Mirrors the compact-path backend dispatch one-for-one so the
    packed exchange composes with every compute mode.  Returns
    (payload [b, p_dev(, Q)] per worker, logical_elems [unreduced])."""
    if use_planned and streamed is not None:
        return _streamed_planned_payload(
            spec, streamed, v_local, n_local, send_rows, axis_name, interpret)
    if use_planned:
        partials = _planned_vertical_partials(
            spec, planned, v_local, n_local, axis_name, interpret)
        payload = packed_rt.gather_payload(spec, partials, send_rows)
        return payload, sparse_exchange.count_non_identity(spec, payload)
    if use_pallas:
        return _ell_partials_payload(
            spec, ell, v_local, n_local, send_rows, axis_name, interpret)
    pay = partial(block_gimv_partials_payload, spec, n_local=n_local)
    if axis_name is not None:
        return pay(stripe, v_local, send_rows)
    return jax.vmap(lambda s, v, sr: pay(s, v, sr))(stripe, v_local, send_rows)


def hierarchical_exchange(spec: GimvSpec, idx, val, n_local: int, axis_name, *,
                          scatter: str = "segment", interpret: bool = False):
    """Two-hop topology-aware exchange (beyond-paper, DESIGN §6 / §Perf).

    axis_name = (pod_axis, *intra_axes).  Partial rows are ordered by global
    destination worker g = p*W + w (shard_map row-major axis order).

    hop 1 (fast intra-pod links): all_to_all over the intra axes so worker w
    collects its pod's W partials for every destination pod, then combineAll
    folds them into ONE [P, n_local] tensor — deduplicating overlapping
    destinations before the slow hop.
    hop 2 (slow inter-pod links): all_to_all of the combined [P, n_local]
    rows over the pod axis, then the final combine.

    Inter-pod volume drops from W*cap*(idx+val) to n_local values: ~12x at
    ClueWeb12 scale (see EXPERIMENTS §Perf).  Returns (r [n_local(, Q)],
    stats).

    A trailing query axis on ``val`` ([b, cap, Q] riding one shared index set
    per partial row, the serving wire format) is carried through both hops:
    hop 1 ships Q values per shipped index, hop 2 ships the combined
    [n_local, Q] rows.
    """
    pod_axis, inner = axis_name[0], tuple(axis_name[1:])
    n_pods = lax.psum(1, pod_axis)
    w_size = lax.psum(1, inner)
    cap = idx.shape[-1]
    nq = val.shape[-1] if val.ndim == idx.ndim + 1 else None
    idx3 = idx.reshape(n_pods, w_size, cap)
    val3 = val.reshape((n_pods, w_size, cap) + (() if nq is None else (nq,)))
    # hop 1: split the intra-pod destination axis, gather per-source rows
    idx_r = lax.all_to_all(idx3, inner, split_axis=1, concat_axis=1, tiled=True)
    val_r = lax.all_to_all(val3, inner, split_axis=1, concat_axis=1, tiled=True)
    # combine the W intra-pod partials per destination pod: the plan's
    # receive-side tactic; scatter_partials folds the leading pod dim itself
    per_pod = sparse_exchange.scatter_partials(
        spec, idx_r, val_r.astype(spec.dtype), n_local,
        method=scatter, interpret=interpret)                     # [P, n_local(, Q)]
    # hop 2: cross-pod exchange of the combined dense rows
    received = lax.all_to_all(per_pod, pod_axis, split_axis=0, concat_axis=0)
    if spec.combine_all == "sum":
        r = jnp.sum(received, axis=0)
    elif spec.combine_all == "min":
        r = jnp.min(received, axis=0)
    else:
        r = jnp.max(received, axis=0)
    stats = {  # GLOBAL elements per iteration; idx word + (1 or Q) value words
        "intra_pod_elems": jnp.asarray(
            float(n_pods) ** 2 * w_size * (w_size - 1) * cap * (1 + (nq or 1)), jnp.float32),
        "inter_pod_elems": jnp.asarray(
            float(n_pods) * (n_pods - 1) * w_size * n_local * (nq or 1), jnp.float32),
    }
    return r, stats


# --------------------------------------------------------------------------
# Placement steps.  All take/return the worker-local vector shard v_local
# [n_local] (emulation: [b, n_local]) and return (v_new_local, r_local, stats).
# --------------------------------------------------------------------------

def _apply_assign(spec, v_local, r_local, ctx_local, real_mask):
    v_new = spec.assign(v_local, r_local, ctx_local)
    if v_new.ndim > real_mask.ndim:  # multi-query: broadcast over Q
        real_mask = real_mask[..., None]
    return jnp.where(real_mask, v_new, v_local)  # padding ids frozen


# Public alias: the disk-residency executor (repro.store.residency) applies
# the identical assign + padding-freeze as the resident placements.
apply_assign = _apply_assign


def _num_queries(v_local, axis_name) -> int | None:
    """Trailing query-axis size, or None for the classic single-vector path.

    Worker-local vectors are [n_local] in SPMD / [b, n_local] in emulation;
    one extra trailing axis means multi-query."""
    expected = 2 if axis_name is None else 1
    return v_local.shape[-1] if v_local.ndim == expected + 1 else None


def horizontal_step(spec: GimvSpec, stripe: BlockEdges, v_local, ctx_local, real_mask, *,
                    n_local: int, axis_name, ell: EllStripe | None = None,
                    planned: PlannedStripe | None = None,
                    backend: str = "xla", interpret: bool = False):
    """Alg. 1: gather the whole vector, compute row stripe locally."""
    nq = _num_queries(v_local, axis_name)
    if backend == "planned" and planned is not None:
        r = _planned_merged_gimv(spec, planned, v_local, n_local, axis_name, interpret)
        if axis_name is not None:
            v_new = _apply_assign(spec, v_local, r, ctx_local, real_mask)
        else:
            v_new = jax.vmap(partial(_apply_assign, spec))(v_local, r, ctx_local, real_mask)
    elif backend == "pallas" and ell is not None:
        r = _ell_gathered_gimv(spec, ell, v_local, n_local, axis_name, interpret)
        if axis_name is not None:
            v_new = _apply_assign(spec, v_local, r, ctx_local, real_mask)
        else:
            v_new = jax.vmap(partial(_apply_assign, spec))(v_local, r, ctx_local, real_mask)
    else:
        v_all = _all_gather(v_local, axis_name)  # [b, n_local(, Q)]

        def compute(stripe_, v_all_, v_local_, ctx_, mask_):
            r_ = gathered_gimv(spec, stripe_, v_all_, n_local)
            return _apply_assign(spec, v_local_, r_, ctx_, mask_), r_

        fn = compute if axis_name is not None else jax.vmap(compute)
        v_new, r = fn(stripe, v_all, v_local, ctx_local, real_mask)
    b = stripe.count.shape[-1]
    vb = jnp.dtype(spec.dtype).itemsize
    stats = {  # GLOBAL elements per iteration (all workers)
        "gathered_elems": jnp.asarray(b * (b - 1) * n_local * (nq or 1), jnp.float32),
        "exchanged_elems": jnp.asarray(0.0, jnp.float32),
        "gathered_bytes": jnp.asarray(
            b * (b - 1) * n_local * (nq or 1) * vb, jnp.float32),
        "exchanged_bytes": jnp.asarray(0.0, jnp.float32),
    }
    return v_new, r, stats


def vertical_step(
    spec: GimvSpec,
    stripe: BlockEdges,
    v_local,
    ctx_local,
    real_mask,
    *,
    n_local: int,
    axis_name,
    exchange: str = "sparse",
    capacity: int | None = None,
    payload_dtype=None,
    ell: EllStripe | None = None,
    planned: PlannedStripe | None = None,
    streamed: PlannedStripe | None = None,
    xchg: dict | None = None,
    xplan=None,
    delta_eps: float | None = None,
    delta_state=None,
    backend: str = "xla",
    scatter: str = "segment",
    interpret: bool = False,
):
    """Alg. 2: local column-stripe partials, exchange, combine at the owner.

    exchange='dense': all_to_all the full [b, n_local] partials (what dense
    collectives would do).  exchange='sparse': compact to (idx, val) pairs of
    static ``capacity`` first — the paper's "only non-empty v^(i,j) entries
    hit the distributed storage".  exchange='packed': ship structure-free
    payloads in the prepare()-time static per-pair row order (``xchg`` holds
    the send/recv index arrays, ``xplan`` the repro.exchange.ExchangePlan
    byte model); with ``delta_state`` (the previously-shipped payload) rows
    that moved <= ``delta_eps`` are suppressed and the step returns a fourth
    element, the new state.  exchange='hier': sparse hop within the
    pod + combined dense hop across pods (needs a tuple axis_name whose
    first element is the pod axis; SPMD only).  A trailing query axis on
    v_local batches all exchanges (hier ships [cap, Q] values on one shared
    index set per hop, like the flat sparse exchange).

    backend='planned' computes the partials through the ExecutionPlan's
    per-block tactics: ``planned`` is the fused same-tactic packing
    (materialize all partials, compact once), ``streamed`` the
    per-destination-block packing the bucket-streamed executor scans
    (plan.stream='on'; compact exchanges only — the dense exchange ships the
    full partials and keeps the fused layout); ``scatter`` picks the
    receive-side combine (segment op | Pallas kernel).
    """
    nq = _num_queries(v_local, axis_name)
    use_pallas = backend == "pallas" and ell is not None
    use_planned = backend == "planned" and (planned is not None or streamed is not None)

    def _planned_compact(v_):
        if streamed is not None:
            return _streamed_planned_compact(
                spec, streamed, v_, n_local, capacity, axis_name, interpret)
        partials_ = _planned_vertical_partials(
            spec, planned, v_, n_local, axis_name, interpret)
        return sparse_exchange.compact_partials(
            spec, partials_, capacity, None, batched=nq is not None)

    if exchange == "hier":
        assert axis_name is not None and isinstance(axis_name, tuple) and len(axis_name) >= 2
        assert capacity is not None
        if use_planned:
            idx, val, overflow, logical = _planned_compact(v_local)
        elif use_pallas:
            idx, val, overflow, logical = _ell_partials_compact(
                spec, ell, v_local, n_local, capacity, axis_name, interpret)
        else:
            compact = partial(block_gimv_partials_compact, spec, n_local=n_local, capacity=capacity)
            idx, val, overflow, logical = compact(stripe, v_local)
        if payload_dtype is not None:
            val = val.astype(payload_dtype)
        overflow = lax.psum(overflow, axis_name)
        logical = lax.psum(logical, axis_name)
        r, hstats = hierarchical_exchange(spec, idx, val, n_local, axis_name,
                                          scatter=scatter, interpret=interpret)
        v_new = _apply_assign(spec, v_local, r, ctx_local, real_mask)
        # wire bytes: intra slots ship an int32 index + payload values, the
        # inter hop ships combined dense partials in the spec dtype.
        intra_slots = hstats["intra_pod_elems"] / (1.0 + (nq or 1))
        stats = {
            "gathered_elems": jnp.asarray(0.0, jnp.float32),
            "exchanged_elems": hstats["intra_pod_elems"] + hstats["inter_pod_elems"],
            **hstats,
            "gathered_bytes": jnp.asarray(0.0, jnp.float32),
            "exchanged_bytes": (
                intra_slots * (4.0 + (nq or 1) * val.dtype.itemsize)
                + hstats["inter_pod_elems"] * jnp.dtype(spec.dtype).itemsize),
            "logical_elems": logical,
            "overflow": overflow,
        }
        return v_new, r, stats
    if exchange == "dense":
        if use_planned:
            # the dense exchange all_to_alls the FULL partials — there is
            # nothing to stream; the engine packs the fused layout for it.
            assert planned is not None, "dense exchange needs the fused planned layout"
            partials = _planned_vertical_partials(
                spec, planned, v_local, n_local, axis_name, interpret)
        elif use_pallas:
            partials = _ell_block_partials(spec, ell, v_local, n_local, axis_name, interpret)
        else:
            compute = partial(block_gimv_partials, spec, n_local=n_local)
            fn = compute if axis_name is not None else jax.vmap(lambda s, v: compute(s, v))
            partials = fn(stripe, v_local)  # [b, n_local(, Q)] per worker
        received = _all_to_all(partials, axis_name)  # [b, n_local(, Q)]
        reduce_axis = -2 if nq is None else -3

        def combine_fn(rcv):
            if spec.combine_all == "sum":
                return jnp.sum(rcv, axis=reduce_axis)
            if spec.combine_all == "min":
                return jnp.min(rcv, axis=reduce_axis)
            return jnp.max(rcv, axis=reduce_axis)

        r = combine_fn(received)
        logical = sparse_exchange.count_non_identity(spec, partials)
        b = stripe.count.shape[-1]
        stats = {  # GLOBAL elements per iteration
            "gathered_elems": jnp.asarray(0.0, jnp.float32),
            "exchanged_elems": jnp.asarray(b * (b - 1) * n_local * (nq or 1), jnp.float32),
            "gathered_bytes": jnp.asarray(0.0, jnp.float32),
            "exchanged_bytes": jnp.asarray(
                b * (b - 1) * n_local * (nq or 1) * partials.dtype.itemsize,
                jnp.float32),
            "logical_elems": logical,
        }
    elif exchange == "packed":
        assert xchg is not None and xplan is not None, \
            "packed exchange needs the prepare()-built index arrays + plan"
        send_rows = xchg["send_rows"]
        payload, logical = _packed_payload(
            spec, v_local, n_local, send_rows,
            stripe=stripe, ell=ell, planned=planned, streamed=streamed,
            use_planned=use_planned, use_pallas=use_pallas,
            axis_name=axis_name, interpret=interpret)
        if axis_name is not None:
            logical = lax.psum(jnp.sum(logical), axis_name)
        else:
            logical = jnp.sum(logical)
        if payload_dtype is not None:
            payload = payload.astype(payload_dtype)  # wire format BEFORE delta
        itemsize = payload.dtype.itemsize
        if delta_state is not None:
            pair_mask = packed_rt.pair_slot_mask(send_rows, n_local, axis_name)
            payload, sent, suppressed = packed_rt.delta_update(
                spec, payload, delta_state, delta_eps or 0.0, pair_mask, axis_name)
            delta_state_new = payload
            payload_bytes = sent * float((nq or 1) * itemsize) \
                + float(xplan.bitmap_bytes)
        else:
            payload_bytes = jnp.asarray(
                xplan.payload_bytes_per_iter(nq, itemsize), jnp.float32)
        val_x = _all_to_all(payload, axis_name)
        r = packed_rt.scatter_payload(
            spec, val_x.astype(spec.dtype), n_local,
            recv_rows=xchg.get("recv_rows"), recv_words=xchg.get("recv_words"),
            p_dev=xplan.p_dev, width=xplan.width_dev,
            method=scatter, interpret=interpret)
        b = send_rows.shape[-2]
        stats = {  # GLOBAL elements; payload values only, ids shipped once
            "gathered_elems": jnp.asarray(0.0, jnp.float32),
            "exchanged_elems": jnp.asarray(
                b * (b - 1) * xplan.p_dev * (nq or 1), jnp.float32),
            "gathered_bytes": jnp.asarray(0.0, jnp.float32),
            "exchanged_bytes": jnp.asarray(payload_bytes, jnp.float32),
            "exchange_payload_bytes": jnp.asarray(payload_bytes, jnp.float32),
            "exchange_id_bytes": jnp.asarray(float(xplan.id_bytes), jnp.float32),
            "logical_elems": logical,
            "overflow": jnp.asarray(0.0, jnp.float32),
        }
        if delta_state is not None:
            stats["delta_sent_rows"] = sent
            stats["delta_suppressed_rows"] = suppressed
    else:
        assert capacity is not None, "sparse exchange needs a static capacity"
        if use_planned:
            idx, val, overflow, logical = _planned_compact(v_local)
        elif use_pallas:
            idx, val, overflow, logical = _ell_partials_compact(
                spec, ell, v_local, n_local, capacity, axis_name, interpret)
        else:
            compact = partial(block_gimv_partials_compact, spec, n_local=n_local, capacity=capacity)
            fn_c = compact if axis_name is not None else jax.vmap(lambda s, v: compact(s, v))
            idx, val, overflow, logical = fn_c(stripe, v_local)
        if payload_dtype is not None:
            val = val.astype(payload_dtype)  # wire format (§Perf); f32 accumulate
        if axis_name is not None:
            overflow = lax.psum(overflow, axis_name)
            logical = lax.psum(logical, axis_name)
        else:
            overflow, logical = jnp.sum(overflow), jnp.sum(logical)
        idx_x = _all_to_all(idx, axis_name)
        val_x = _all_to_all(val, axis_name)
        # receive side: the plan's scatter tactic (segment op | Pallas kernel);
        # leading (emulation worker) dims are handled inside scatter_partials.
        r = sparse_exchange.scatter_partials(
            spec, idx_x.astype(jnp.int32), val_x.astype(spec.dtype), n_local,
            method=scatter, interpret=interpret)
        b = idx.shape[-2]
        id_b, pay_b = sparse_exchange.exchange_wire_split(
            b, capacity, nq, val.dtype.itemsize)
        stats = {  # GLOBAL elements; idx word + (1 or Q) value words per slot
            "gathered_elems": jnp.asarray(0.0, jnp.float32),
            "exchanged_elems": jnp.asarray(b * (b - 1) * capacity * (1 + (nq or 1)), jnp.float32),
            "gathered_bytes": jnp.asarray(0.0, jnp.float32),
            "exchanged_bytes": jnp.asarray(
                sparse_exchange.exchange_wire_bytes(
                    b, capacity, nq, val.dtype.itemsize), jnp.float32),
            # the padded stream re-ships its int32 ids EVERY iteration
            "exchange_id_bytes": jnp.asarray(id_b, jnp.float32),
            "exchange_payload_bytes": jnp.asarray(pay_b, jnp.float32),
            "logical_elems": logical,
            "overflow": overflow,
        }

    if axis_name is not None:
        v_new = _apply_assign(spec, v_local, r, ctx_local, real_mask)
    else:
        v_new = jax.vmap(partial(_apply_assign, spec))(v_local, r, ctx_local, real_mask)
    if delta_state is not None:
        return v_new, r, stats, delta_state_new
    return v_new, r, stats


def hybrid_step(
    spec: GimvSpec,
    sparse_stripe: BlockEdges,
    dense_stripe: BlockEdges,
    dense_region: DenseRegion,
    v_local,
    ctx_local,
    real_mask,
    *,
    n_local: int,
    axis_name,
    capacity: int,
    exchange: str = "sparse",
    payload_dtype=None,
    sparse_ell: EllStripe | None = None,
    planned_sparse: PlannedStripe | None = None,
    streamed_sparse: PlannedStripe | None = None,
    xchg: dict | None = None,
    xplan=None,
    dense_matrix=None,
    backend: str = "xla",
    scatter: str = "segment",
    interpret: bool = False,
):
    """Alg. 4: vertical over the sparse region + horizontal over the dense
    region, combined at the owner, then assign.

    The dense sub-vector v_d is the compacted gather of high-out-degree
    entries: [d_cap] per worker -> all_gather -> [b, d_cap]; its edges index
    it with (block, slot) pairs.  backend='pallas' runs the sparse region
    through the ELL kernel and the dense region as a semiring matmul against
    the materialized ``dense_matrix`` [n_local, b*d_cap]; backend='planned'
    runs the sparse region per the ExecutionPlan's block tactics — fused
    (``planned_sparse``) or bucket-streamed per destination block
    (``streamed_sparse``, plan.stream='on') — and keeps the kernelized dense
    region (it IS the region-level dense tactic).  ``scatter`` picks the
    receive-side combine.
    """
    # -- dense region: extract + all_gather the (small) dense sub-vector.
    # gather_idx is per-worker in SPMD ([d_cap]) / [b, d_cap] in emulation.
    nq = _num_queries(v_local, axis_name)
    use_planned = backend == "planned" and (
        planned_sparse is not None or streamed_sparse is not None)
    use_dense_kernel = backend in ("pallas", "planned") and dense_matrix is not None
    use_pallas = backend == "pallas" and sparse_ell is not None and dense_matrix is not None
    if axis_name is not None:
        v_d = v_local[dense_region.gather_idx]  # [d_cap(, Q)]
    elif nq is not None:
        v_d = jnp.take_along_axis(v_local, dense_region.gather_idx[:, :, None], axis=1)
    else:
        v_d = jnp.take_along_axis(v_local, dense_region.gather_idx, axis=1)

    if use_dense_kernel:
        r_dense = _dense_region_gimv(spec, dense_matrix, v_d, n_local, axis_name, interpret)
    else:
        v_d_all = _all_gather(v_d, axis_name)  # [b, d_cap(, Q)]
        if axis_name is not None:
            r_dense = gathered_gimv(spec, dense_stripe, v_d_all, n_local)
        else:
            r_dense = jax.vmap(lambda s, va: gathered_gimv(spec, s, va, n_local))(
                dense_stripe, v_d_all)

    # -- sparse region: vertical partials + compact or packed exchange.
    if exchange == "packed":
        assert xchg is not None and xplan is not None, \
            "packed exchange needs the prepare()-built index arrays + plan"
        send_rows = xchg["send_rows"]
        payload, logical = _packed_payload(
            spec, v_local, n_local, send_rows,
            stripe=sparse_stripe, ell=sparse_ell, planned=planned_sparse,
            streamed=streamed_sparse, use_planned=use_planned,
            use_pallas=use_pallas, axis_name=axis_name, interpret=interpret)
        if axis_name is not None:
            logical = lax.psum(jnp.sum(logical), axis_name)
        else:
            logical = jnp.sum(logical)
        if payload_dtype is not None:
            payload = payload.astype(payload_dtype)
        wire_itemsize = payload.dtype.itemsize
        overflow = jnp.asarray(0.0, jnp.float32)
        val_x = _all_to_all(payload, axis_name)
        r_sparse = packed_rt.scatter_payload(
            spec, val_x.astype(spec.dtype), n_local,
            recv_rows=xchg.get("recv_rows"), recv_words=xchg.get("recv_words"),
            p_dev=xplan.p_dev, width=xplan.width_dev,
            method=scatter, interpret=interpret)
        b = send_rows.shape[-2]
        exchanged_elems = b * (b - 1) * xplan.p_dev * (nq or 1)
        id_b = float(xplan.id_bytes)
        pay_b = xplan.payload_bytes_per_iter(nq, wire_itemsize)
        exchanged_bytes = pay_b
    else:
        if use_planned and streamed_sparse is not None:
            idx, val, overflow, logical = _streamed_planned_compact(
                spec, streamed_sparse, v_local, n_local, capacity, axis_name, interpret)
        elif use_planned:
            partials = _planned_vertical_partials(
                spec, planned_sparse, v_local, n_local, axis_name, interpret)
            idx, val, overflow, logical = sparse_exchange.compact_partials(
                spec, partials, capacity, None, batched=nq is not None)
        elif use_pallas:
            idx, val, overflow, logical = _ell_partials_compact(
                spec, sparse_ell, v_local, n_local, capacity, axis_name, interpret)
        else:
            compact = partial(block_gimv_partials_compact, spec, n_local=n_local, capacity=capacity)
            fn_c = compact if axis_name is not None else jax.vmap(lambda s, v: compact(s, v))
            idx, val, overflow, logical = fn_c(sparse_stripe, v_local)
        if payload_dtype is not None:
            val = val.astype(payload_dtype)  # wire format (§Perf); accumulate in spec dtype
        if axis_name is not None:
            overflow = lax.psum(overflow, axis_name)
            logical = lax.psum(logical, axis_name)
        else:
            overflow, logical = jnp.sum(overflow), jnp.sum(logical)
        idx_x = _all_to_all(idx, axis_name)
        val_x = _all_to_all(val, axis_name)

        # owner combine: plan-selected receive-side scatter.
        r_sparse = sparse_exchange.scatter_partials(
            spec, idx_x.astype(jnp.int32), val_x.astype(spec.dtype), n_local,
            method=scatter, interpret=interpret)
        b = idx.shape[-2]
        exchanged_elems = b * (b - 1) * capacity * (1 + (nq or 1))
        exchanged_bytes = sparse_exchange.exchange_wire_bytes(
            b, capacity, nq, val.dtype.itemsize)
        id_b, pay_b = sparse_exchange.exchange_wire_split(
            b, capacity, nq, val.dtype.itemsize)

    # elementwise combineAll with the dense region, then assign.
    r = combine_elementwise(spec, r_sparse, r_dense)
    if axis_name is not None:
        v_new = _apply_assign(spec, v_local, r, ctx_local, real_mask)
    else:
        v_new = jax.vmap(partial(_apply_assign, spec))(v_local, r, ctx_local, real_mask)

    d_cap = dense_region.d_cap
    stats = {  # GLOBAL elements per iteration
        "gathered_elems": jnp.asarray(b * (b - 1) * d_cap * (nq or 1), jnp.float32),
        "exchanged_elems": jnp.asarray(exchanged_elems, jnp.float32),
        "gathered_bytes": jnp.asarray(
            b * (b - 1) * d_cap * (nq or 1) * jnp.dtype(spec.dtype).itemsize,
            jnp.float32),
        "exchanged_bytes": jnp.asarray(exchanged_bytes, jnp.float32),
        "exchange_id_bytes": jnp.asarray(id_b, jnp.float32),
        "exchange_payload_bytes": jnp.asarray(pay_b, jnp.float32),
        "logical_elems": logical,
        "overflow": overflow,
    }
    return v_new, r, stats
