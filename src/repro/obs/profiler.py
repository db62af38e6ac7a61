"""Per-block launch profiler: measured kernel time for every planned block.

The planner prices each pre-partitioned b x b sub-block in abstract slot
units (cost_model.ell_block_cost / dense_block_cost) but the fused planned
step launches whole same-tactic groups, so per-block wall time is invisible
from inside the jitted path.  This module re-runs each non-skip block's
kernel launch STANDALONE — the same row-bucketed ELL tables
(blocks.pack_bucketed_ell -> kernels.ell_gimv) and materialized dense
matrices (blocks.materialize_dense_block -> kernels.dense_gimv) the planned
packer builds — under ``launch.ell`` / ``launch.dense`` spans carrying the
plan's prediction, which is exactly what :mod:`repro.obs.report` joins into
per-kind calibration residuals for BENCH_obs.json.

Standalone launches measure the kernels without the fused group's scatter
tail, so treat the residuals as per-tactic unit costs (seconds per slot),
not end-to-end step predictions — the step-level comparison lives in
``PMVEngine.explain(live=True)``.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core import blocks as blocks_lib
from repro.core import placement
from repro.kernels.block_gimv import has_semiring, semiring_of
from repro.obs.recorder import Recorder, as_recorder

__all__ = ["profile_block_launches"]


def profile_block_launches(engine, spec, ctx: dict | None = None, *,
                           repeats: int = 1, obs=None) -> Recorder:
    """Time every non-skip planned block's kernel launch in isolation.

    Prepares (and caches) the engine's solve, then walks the ExecutionPlan's
    block grid: each 'ell' block is packed into its row-bucketed ELL tables
    and dispatched through the semiring ELL kernel; each 'dense' block is
    materialized and dispatched through the dense MXU kernel.  Every timed
    launch is compiled/warmed first, then recorded ``repeats`` times as a
    ``launch.<tactic>`` span with ``plan.block_attrs(i, j)`` attached
    (predicted_cost in slots, predicted_s via SLOT_TIME_S).

    Returns the recorder (a fresh enabled one unless ``obs`` is given).
    """
    rec = as_recorder(True if obs is None else obs)
    if not has_semiring(spec.combine2, spec.combine_all):
        raise ValueError(
            f"spec {spec.name!r} has no kernel semiring — per-block kernel "
            "launches cannot be profiled (the planned backend would also "
            "degrade to 'xla' here)")
    _step, _matrix, _v0, _ctx, _mask, meta = engine.prepare(spec, ctx)
    plan, pm, hm, part = meta["plan"], meta["pm"], meta["hm"], meta["part"]
    if pm is None:
        raise ValueError(
            "residency='disk' never materializes the stripes; profile a "
            "resident engine over the same store (residency='host') — the "
            "disk path's launches are traced live as 'launch.disk_block'")
    # worker j's vertical stripe holds blocks (i, j) with inner axis i, the
    # same (dest, src) indexing as plan.block(i, j); hybrid plans price the
    # sparse region, whose stripes share that layout.
    stripes = hm.sparse_vertical if hm is not None else pm.vertical
    semiring = semiring_of(spec.combine2, spec.combine_all)
    interpret = meta["cfg"].interpret
    n_local = part.n_local
    # deterministic non-trivial operand (values are irrelevant to timing)
    v = jnp.asarray(np.linspace(0.1, 1.0, n_local), spec.dtype)

    for j, stripe in enumerate(stripes):
        counts = np.asarray(stripe.count)
        seg = np.asarray(stripe.seg_local)
        gat = np.asarray(stripe.gat_local)
        www = np.asarray(stripe.w) if stripe.w is not None else None
        for i in range(part.b):
            bp = plan.block(i, j)
            cnt = int(counts[i])
            if bp.tactic == "skip" or cnt == 0:
                continue
            dst, src = seg[i, :cnt], gat[i, :cnt]
            wij = www[i, :cnt] if www is not None else None
            attrs = plan.block_attrs(i, j)
            if bp.tactic == "dense":
                m2d = jnp.asarray(blocks_lib.materialize_dense_block(
                    dst, src, wij, n_local, semiring))

                def launch(m2d=m2d):
                    return placement._planned_dense_call(spec, m2d, v, interpret)

                name = "launch.dense"
            else:
                tables = [
                    (jnp.asarray(bk.cols),
                     None if bk.w is None else jnp.asarray(bk.w))
                    for bk in blocks_lib.pack_bucketed_ell(
                        dst, src, wij, plan.boundaries, split=plan.split)
                    if bk.rows.size]

                def launch(tables=tables):
                    return [placement.ell_gimv_call(spec, cols, w, v, interpret)
                            for cols, w in tables]

                name = "launch.ell"
            rec.fence(launch())          # compile + warm outside the span
            for _ in range(repeats):
                with rec.span(name, attrs):
                    rec.fence(launch())
    return rec
