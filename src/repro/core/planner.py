"""Per-block execution planner: density-driven ExecutionPlan (tentpole).

The paper's central claim is that PMV wins by "judiciously applying execution
strategies based on the density of the pre-partitioned sub-matrices".  The
engine used to pick ONE strategy and ONE backend globally per solve; this
module closes the gap with a three-stage pipeline:

    planner (here)  ->  ExecutionPlan (static, hashable)  ->  executor

At ``PMVEngine.prepare()`` time every b x b sub-block M^(i,j) is measured
(nnz, max in-degree, flat-ELL padding occupancy) and classified with the
cost model (cost_model.ell_block_cost / dense_block_cost) into a tactic:

    skip  — structurally empty: dropped at pack time, zero per-iteration cost;
    ell   — sparse kernel over ROW-BUCKETED ELL slices (degree buckets with
            power-of-two widths cut the padding a skewed block pays under one
            global d_cap).  The widths stop at a cap K that ``ell_layout``
            picks from the degree histograms: a row wider than K splits into
            ceil(d/K) rows of the K bucket, whose results combine with
            combineAll, so one hub row no longer pads every stacked table of
            a wide bucket.  Where the model prefers no cap, the widths run
            up to d_max and nothing splits;
    dense — near-dense block materialized as a [n_local, n_local] semiring
            matrix for the MXU kernel.

The resulting :class:`ExecutionPlan` is a frozen, hashable pytree-of-metadata
that ``blocks.pack_planned_stripe`` packs against, the ``placement._planned_*``
executors run by grouping same-tactic blocks into fused kernel launches, and
``engine.py`` / ``repro.serving`` consume in place of the former global
``backend=`` branching (``backend='xla' | 'pallas'`` remain as forced
overrides, recorded as plan modes; ``backend='auto'`` engages the planner).

The plan also carries the receive-side tactic of the sparse exchange
(``scatter``): 'segment' (the XLA segment-combine) or 'kernel' (the Pallas
scatter-combine kernel, kernels/scatter_combine) — 'auto' resolves through
the cost model's T*n_out-vs-serial-scatter crossover
(cost_model.prefer_kernel_scatter) — and the partial-vector schedule of the
vertical/hybrid step (``stream``): 'off' materializes all b destination-block
partials before compaction (fused same-tactic launches), 'on' scans
destination blocks per the plan's launch schedule and compacts each partial
immediately, restoring the paper Alg. 2's O(n_local + b*cap) live-memory
profile — 'auto' resolves via cost_model.prefer_streamed, so tiny b keeps
the fused fast path.  ``memory_profile()`` reports both estimates and
``format_plan`` / ``PMVEngine.explain()`` print them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import cost_model
from repro.core.blocks import BlockEdges
from repro.core.sparse_exchange import SCATTER_METHODS
from repro.kernels.ell_spmv import ell_rows, ell_width

__all__ = [
    "BlockPlan",
    "ExecutionPlan",
    "bucket_boundaries",
    "ell_layout",
    "ell_layout_seconds",
    "ell_tables",
    "measure_blocks",
    "plan_execution",
    "plan_from_stats",
    "format_plan",
    "TACTICS",
    "MODES",
    "STREAM_MODES",
    "RESIDENCY_MODES",
]

TACTICS = ("skip", "ell", "dense")
# Power-of-two ELL widths 1 .. 2^15: a Graph500 scale-22 graph's per-block
# in-degrees stay below that, so no bucket drops from the narrow end, where
# the bulk of rows (in-degree 1-2 within a block) would pad to the narrowest
# kept width.
MAX_BUCKETS = 16
MODES = ("xla", "pallas", "planned")
STREAM_MODES = ("on", "off")
RESIDENCY_MODES = cost_model.RESIDENCY_MODES


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Measured stats + chosen tactic for one pre-partitioned sub-block."""

    i: int               # destination (segment) block
    j: int               # source (gather) block
    tactic: str          # 'skip' | 'ell' | 'dense'
    nnz: int             # edges in M^(i,j)
    rows: int            # destination rows with >= 1 edge
    d_max: int           # max in-degree within the block
    occupancy: float     # nnz / (rows * d_max): flat-ELL slot occupancy
    cost: float          # predicted per-iteration compute cost (slot units)
    bucket_rows: tuple[int, ...] = ()  # rows per ELL degree bucket (ell tactic)


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Static, hashable execution plan for one prepared solve.

    mode: 'planned' runs the per-block tactics; 'xla' / 'pallas' record the
    forced global overrides (their executors ignore the tactic table, but
    ``PMVEngine.explain()`` still reports it).
    """

    strategy: str                   # 'horizontal' | 'vertical' | 'hybrid'
    mode: str                       # 'xla' | 'pallas' | 'planned'
    b: int
    n_local: int
    theta: float | None
    capacity: int | None
    boundaries: tuple[int, ...]     # bucket width boundaries (ascending)
    blocks: tuple[BlockPlan, ...]   # b*b entries, row-major (i, j)
    scatter: str = "segment"        # receive-side tactic: 'segment' | 'kernel'
    stream: str = "off"             # partial schedule: 'on' (bucket-streamed) | 'off'
    residency: str = "device"       # matrix home: 'device' | 'host' | 'disk'
    e_cap: int | None = None        # padded edge capacity of the shard slices
    split: bool = False             # rows wider than boundaries[-1] split

    def __post_init__(self):
        assert self.mode in MODES, self.mode
        assert self.scatter in SCATTER_METHODS, self.scatter
        assert self.stream in STREAM_MODES, self.stream
        assert self.residency in RESIDENCY_MODES, self.residency
        assert len(self.blocks) == self.b * self.b, (len(self.blocks), self.b)

    def block(self, i: int, j: int) -> BlockPlan:
        return self.blocks[i * self.b + j]

    @property
    def ell_cap(self) -> int:
        """K: the widest ELL bucket.  With ``split`` a row of in-degree
        d > K packs as ceil(d/K) rows of that bucket."""
        return self.boundaries[-1]

    def tactics_for_worker(self, worker: int, layout: str) -> tuple[str, ...]:
        """Per-inner-block tactics of one worker's stripe.

        layout='vertical': worker j owns blocks (i, j), inner axis = i.
        layout='merged': worker i owns blocks (i, jj), inner axis = jj.
        """
        if layout == "vertical":
            return tuple(self.block(i, worker).tactic for i in range(self.b))
        return tuple(self.block(worker, jj).tactic for jj in range(self.b))

    def tactic_counts(self) -> dict[str, int]:
        out = {t: 0 for t in TACTICS}
        for bp in self.blocks:
            out[bp.tactic] += 1
        return out

    def launch_schedule(self, worker: int) -> tuple[tuple, ...]:
        """Per-DESTINATION-block launch schedule of one worker's vertical
        stripe — what the streamed executor runs per scan step, and what
        ``blocks.pack_streamed_stripe`` packs against.

        Entry i describes destination block M^(i, worker):
        ('skip',) | ('dense', n_local) | ('ell', rows_per_bucket) where
        rows_per_bucket[k] is the number of destination rows bucket k's
        [R_k, boundaries[k]] table holds for this block (with ``split``,
        the top bucket's count includes split rows as the degree histogram
        estimates them).
        """
        sched = []
        for i in range(self.b):
            bp = self.block(i, worker)
            if bp.tactic == "skip":
                sched.append(("skip",))
            elif bp.tactic == "dense":
                sched.append(("dense", self.n_local))
            else:
                sched.append(("ell", bp.bucket_rows))
        return tuple(sched)

    def block_attrs(self, i: int, j: int) -> dict:
        """Static launch-span attributes of one sub-block: tactic, measured
        shape, and the cost model's prediction — what the obs profiler
        attaches to each ``launch.ell`` / ``launch.dense`` span so the
        predicted-vs-measured report can join without replanning."""
        bp = self.block(i, j)
        return {
            "i": i, "j": j, "tactic": bp.tactic, "nnz": bp.nnz,
            "rows": bp.rows, "d_max": bp.d_max, "occupancy": bp.occupancy,
            "predicted_cost": bp.cost,
            "predicted_s": cost_model.slot_seconds(bp.cost),
        }

    def launch_cost(self, k: int, *, axis: str = "dest") -> float:
        """Predicted slot cost of one launch-schedule step: destination
        block k across every worker stripe (axis='dest', the vertical /
        hybrid schedule) or source block k (axis='src', horizontal).  The
        DiskExecutor attaches this to its per-step launch spans."""
        if axis == "dest":
            return sum(self.block(k, j).cost for j in range(self.b))
        return sum(self.block(i, k).cost for i in range(self.b))

    def launch_attrs(self, k: int, *, axis: str = "dest") -> dict:
        """Static launch-span attributes of one schedule step (see
        :meth:`launch_cost`)."""
        cost = self.launch_cost(k, axis=axis)
        return {"block": k, "axis": axis, "predicted_cost": cost,
                "predicted_s": cost_model.slot_seconds(cost)}

    def memory_profile(self) -> dict:
        """Estimated live partial-buffer elements per worker of the
        vertical/hybrid step: 'materialized' holds all b destination-block
        partials before compaction (O(b * n_local)); 'streamed' holds one
        partial in flight plus the fixed compact exchange buffer
        (O(n_local + b * cap), the paper Alg. 2's profile).  'savings' is
        their ratio; 'stream' echoes the plan's resolved schedule."""
        cap = self.capacity if self.capacity is not None else self.n_local
        mat = cost_model.materialized_partial_elems(self.b, self.n_local)
        strm = cost_model.streamed_partial_elems(self.b, self.n_local, cap)
        return {
            "materialized_elems": mat,
            "streamed_elems": strm,
            "savings": mat / max(strm, 1),
            "stream": self.stream,
        }

    def io_bytes_per_iter(self, *, has_w: bool = False) -> int:
        """Modeled shard bytes READ per iteration under residency='disk':
        one [b, e_cap] seg+gat slice per scheduled (non-empty) destination
        block (vertical/hybrid) or source block (horizontal); 0 when
        resident.  Matches the executor's measured ``store_bytes_read`` —
        weights are recomputed, never read."""
        if self.residency != "disk" or self.e_cap is None:
            return 0
        active = set()
        for bp in self.blocks:
            if bp.nnz:
                active.add(bp.i if self.strategy != "horizontal" else bp.j)
        return len(active) * cost_model.stripe_slice_bytes(
            self.b, self.e_cap, has_w=has_w)

    @property
    def flat_padded_slots(self) -> int:
        """Slots the pre-plan flat layout touches: every non-empty block's
        rows padded to the stripe-global d_cap (what stripe_to_ell packs)."""
        d_cap = max((bp.d_max for bp in self.blocks), default=1)
        return sum(bp.rows * d_cap for bp in self.blocks if bp.nnz)

    @property
    def planned_slots(self) -> float:
        """Predicted slots under the plan (sum of per-block tactic costs)."""
        return sum(bp.cost for bp in self.blocks)


def bucket_boundaries(d_max: int, *, max_buckets: int = MAX_BUCKETS,
                      cap: int | None = None) -> tuple[int, ...]:
    """Power-of-two ELL bucket widths up to d_max, capped at max_buckets
    (dropping from the narrow end: low-degree rows then land in the smallest
    remaining boundary, still correct, just slightly more padded).  The
    widest is d_max at the kernel's slot alignment (``ell_width``).

    ``cap=K`` (a power of two below d_max): the widths stop at K, and rows
    wider than K split into K-wide rows of the top bucket."""
    if cap is not None:
        assert cap & (cap - 1) == 0 and cap < d_max, (cap, d_max)
        return tuple(1 << k for k in range(cap.bit_length()))[-max_buckets:]
    bounds = []
    d = 1
    while d < max(d_max, 1):
        bounds.append(d)
        d *= 2
    bounds.append(ell_width(d_max))
    return tuple(bounds[-max_buckets:])


def measure_blocks(
    stripes: list[BlockEdges], b: int, *, stripe_axis: str
) -> list[dict]:
    """Per-block measured stats from per-worker stripes (host numpy).

    stripe_axis='gat' (vertical stripes): stripes[j] inner block k is
    M^(k, j).  stripe_axis='seg' (horizontal stripes): stripes[i] inner block
    k is M^(i, k).  Returns b*b dicts, row-major (i, j), each with nnz, rows
    (non-empty destination rows), d_max, and the degree histogram needed for
    bucketed-slot costing.
    """
    assert stripe_axis in ("gat", "seg")
    out = [None] * (b * b)
    for worker, stripe in enumerate(stripes):
        counts = np.asarray(stripe.count)
        for k in range(b):
            i, j = (k, worker) if stripe_axis == "gat" else (worker, k)
            cnt = int(counts[k])
            if cnt:
                seg = np.asarray(stripe.seg_local[k, :cnt])
                deg = np.bincount(seg)
                deg = deg[deg > 0]
                rec = {"nnz": cnt, "rows": int(deg.size),
                       "d_max": int(deg.max()), "deg": deg}
            else:
                rec = {"nnz": 0, "rows": 0, "d_max": 0,
                       "deg": np.zeros(0, np.int64)}
            out[i * b + j] = rec
    return out


def _merged_deg(stripe: BlockEdges) -> np.ndarray:
    """Per-row in-degrees (non-zero) of a horizontal stripe with all inner
    (source) blocks merged — what the merged ELL layout buckets by."""
    counts = np.asarray(stripe.count)
    segs = [np.asarray(stripe.seg_local[k, : int(counts[k])])
            for k in range(stripe.seg_local.shape[0]) if int(counts[k])]
    if not segs:
        return np.zeros(0, np.int64)
    deg = np.bincount(np.concatenate(segs))
    return deg[deg > 0]


DEG_HIST_BINS = 64  # power-of-two degree histogram width (degrees < 2^63)


def deg_hist_of(deg: np.ndarray) -> np.ndarray:
    """Per-block power-of-two degree histogram: hist[k] = destination rows
    with in-degree in (2^(k-1), 2^k] (k=0: degree exactly 1; the last bin
    catches everything above 2^62 — 2^63 would overflow the int64 boundary
    table).  The store manifest persists these so plans rebuilt from a
    manifest classify blocks bitwise-identically to plans measured from
    in-memory stripes."""
    edges = 1 << np.arange(DEG_HIST_BINS - 1, dtype=np.int64)
    bins = np.searchsorted(edges, np.asarray(deg, dtype=np.int64), side="left")
    return np.bincount(bins, minlength=DEG_HIST_BINS)


def _hist_of(rec: dict) -> np.ndarray:
    """A measurement record's power-of-two degree histogram: the manifest's
    'deg_hist', or the histogram of the measured per-row degrees ('deg').
    Plans read degrees only through it, so both classify identically."""
    if "deg_hist" in rec:
        return np.asarray(rec["deg_hist"], dtype=np.int64)
    return deg_hist_of(rec["deg"])


def _hist_bucket_rows(hist: np.ndarray, boundaries: tuple[int, ...],
                      split: bool = False) -> np.ndarray:
    """Table rows per ELL bucket of degree histograms [..., H].

    Unsplit, this is exact: every degree inside one histogram bin maps to
    the same bucket because the boundary list contains only powers of two
    plus the final d_max, so no boundary falls strictly inside a bin below
    d_max.  With ``split``, a row of degree d > K = boundaries[-1] packs as
    ceil(d/K) rows of the top bucket; a bin (2^(k-1), 2^k] above K holds
    rows of 2^(k-1)/K + 1 to 2^k/K pieces each, counted at the midpoint.
    """
    hist = np.asarray(hist, dtype=np.int64)            # [..., H]
    bounds = np.asarray(boundaries, dtype=np.int64)
    top = int(bounds[-1])
    out = np.zeros(hist.shape[:-1] + (len(boundaries),), dtype=np.int64)
    for k in np.nonzero(hist.reshape(-1, hist.shape[-1]).any(axis=0))[0]:
        rep = int(1) << int(k)                         # the bin's top degree
        if split and rep > top:
            lo, hi = rep // (2 * top) + 1, rep // top
            out[..., -1] += -(-hist[..., k] * (lo + hi) // 2)
        else:
            out[..., int(np.searchsorted(bounds, min(rep, top)))] += hist[..., k]
    return out


def _bucket_rows_of(rec: dict, boundaries: tuple[int, ...],
                    split: bool = False) -> np.ndarray:
    """Rows per ELL degree bucket of one measurement record."""
    return _hist_bucket_rows(_hist_of(rec), boundaries, split)


def ell_layout_seconds(hists: np.ndarray, boundaries: tuple[int, ...],
                       split: bool) -> float:
    """Modelled per-sweep time (cost_model.ell_layout_seconds) of packing
    the T tables whose degree histograms are ``hists`` [T, H] at these
    widths, stacked as the packers stack them: bucket k's row count is the
    largest over the tables at ``ell_rows`` alignment, so every table pays
    for the fullest one."""
    per = _hist_bucket_rows(hists, boundaries, split)  # [T, n_buckets]
    r = np.asarray([ell_rows(int(x)) for x in per.max(axis=0)], np.int64)
    t = len(hists)
    slots = t * int((r * np.asarray(boundaries, np.int64)).sum())
    return cost_model.ell_layout_seconds(slots, t * int(r.sum()))


def ell_tables(recs: list[dict], *, b: int, n_local: int,
               max_buckets: int = MAX_BUCKETS,
               mxu_advantage: float = cost_model.MXU_SLOT_ADVANTAGE):
    """(hists [b*b, H], d_max, unsplit) of a vertical layout's b*b block
    tables, the input of ``ell_layout``: only the blocks that pack as ELL at
    the unsplit widths count (a dense block's rows never reach a bucket),
    though every block's table stacks all the same.  ``unsplit`` are the
    widths up to the d_max of every block."""
    unsplit = bucket_boundaries(max((r["d_max"] for r in recs), default=1),
                                max_buckets=max_buckets)
    ell = [_classify(r, k // b, k % b, n_local, unsplit,
                     mxu_advantage).tactic == "ell"
           for k, r in enumerate(recs)]
    hists = np.stack([_hist_of(r) * e for r, e in zip(recs, ell)])
    d_max = max([1] + [r["d_max"] for r, e in zip(recs, ell) if e])
    return hists, d_max, unsplit


def ell_layout(hists: np.ndarray, d_max: int, *,
               max_buckets: int = MAX_BUCKETS) -> tuple[tuple[int, ...], bool]:
    """(boundaries, split): the ELL widths of least modelled time.

    The candidates are the unsplit widths up to d_max and every power-of-two
    cap K < d_max that fits ``max_buckets``; ``hists`` [T, H] are the degree
    histograms of the tables that stack.  Ties keep the unsplit widths, so
    a graph whose degrees never pay for a cap packs as it always did."""
    best = bucket_boundaries(d_max, max_buckets=max_buckets), False
    best_s = ell_layout_seconds(hists, *best)
    cap = 1
    while cap < d_max and cap.bit_length() <= max_buckets:
        cand = bucket_boundaries(d_max, max_buckets=max_buckets, cap=cap), True
        cand_s = ell_layout_seconds(hists, *cand)
        if cand_s < best_s:
            best, best_s = cand, cand_s
        cap *= 2
    return best


def _classify(
    rec: dict, i: int, j: int, n_local: int, boundaries: tuple[int, ...],
    mxu_advantage: float, io_cost: float = 0.0, split: bool = False,
) -> BlockPlan:
    if rec["nnz"] == 0:
        return BlockPlan(i=i, j=j, tactic="skip", nnz=0, rows=0, d_max=0,
                         occupancy=0.0, cost=0.0)
    bounds = np.asarray(boundaries, dtype=np.int64)
    rows_per_bucket = _bucket_rows_of(rec, boundaries, split)
    ell_cost = cost_model.ell_block_cost(int((rows_per_bucket * bounds).sum()))
    dense_cost = cost_model.dense_block_cost(n_local, mxu_advantage)
    tactic = "dense" if dense_cost < ell_cost else "ell"
    occ = rec["nnz"] / float(rec["rows"] * rec["d_max"])
    bucket_rows = tuple(rows_per_bucket.tolist()) if tactic == "ell" else ()
    return BlockPlan(i=i, j=j, tactic=tactic, nnz=rec["nnz"], rows=rec["rows"],
                     d_max=rec["d_max"], occupancy=round(occ, 4),
                     cost=min(ell_cost, dense_cost) + io_cost,
                     bucket_rows=bucket_rows)


def plan_execution(
    pm,
    hm,
    *,
    strategy: str,
    mode: str,
    theta: float | None = None,
    capacity: int | None = None,
    scatter: str = "auto",
    stream: str = "off",
    max_buckets: int = MAX_BUCKETS,
    mxu_advantage: float = cost_model.MXU_SLOT_ADVANTAGE,
    interpret: bool = False,
    residency: str = "device",
) -> ExecutionPlan:
    """Measure + classify every sub-block of the strategy's stripes.

    pm / hm: PartitionedMatrix / HybridMatrix | None from partition_graph.
    For 'hybrid' the table covers the sparse-region blocks (the dense region
    is a region-level dense tactic by construction, paper §3.5).  The tactic
    table is always built — forced modes ('xla' / 'pallas') carry it for
    ``explain()`` even though their executors ignore it.

    ``stream`` is the RESOLVED partial schedule ('on' | 'off'; the engine
    resolves its 'auto' knob via cost_model.prefer_streamed before planning);
    ``scatter='auto'`` resolves here via the T*n_out-vs-serial crossover.
    """
    if strategy == "hybrid":
        assert hm is not None
        stripes, axis = hm.sparse_vertical, "gat"
    elif strategy == "vertical":
        stripes, axis = pm.vertical, "gat"
    else:
        stripes, axis = pm.horizontal, "seg"
    b = pm.part.b
    n_local = pm.part.n_local

    recs = measure_blocks(stripes, b, stripe_axis=axis)
    merged_d_max = merged_hist = None
    if strategy == "horizontal":
        degs = [_merged_deg(s) for s in stripes]
        merged_d_max = max([1] + [int(d.max()) for d in degs if d.size])
        merged_hist = np.stack([deg_hist_of(d) for d in degs])
    return plan_from_stats(
        recs, b=b, n_local=n_local, strategy=strategy, mode=mode, theta=theta,
        capacity=capacity, scatter=scatter, stream=stream,
        max_buckets=max_buckets, mxu_advantage=mxu_advantage,
        interpret=interpret, residency=residency, merged_d_max=merged_d_max,
        merged_hist=merged_hist)


def plan_from_stats(
    recs: list[dict],
    *,
    b: int,
    n_local: int,
    strategy: str,
    mode: str,
    theta: float | None = None,
    capacity: int | None = None,
    scatter: str = "auto",
    stream: str = "off",
    max_buckets: int = MAX_BUCKETS,
    mxu_advantage: float = cost_model.MXU_SLOT_ADVANTAGE,
    interpret: bool = False,
    residency: str = "device",
    merged_d_max: int | None = None,
    merged_hist: np.ndarray | None = None,
) -> ExecutionPlan:
    """Build an ExecutionPlan from per-block measurement records.

    ``recs`` is the b*b row-major list from :func:`measure_blocks` — or its
    persisted form reconstructed from a store manifest, where each record
    carries the power-of-two degree histogram ('deg_hist', deg_hist_of)
    instead of the raw per-row degrees; both classify identically
    (_bucket_rows_of), so a plan rebuilt from a manifest equals the plan
    measured from the in-memory stripes.  ``merged_d_max`` and
    ``merged_hist`` ([b, H], one histogram per worker of full per-row
    in-degrees) override the bucket sizing for the horizontal merged layout.
    The ELL widths come from ``ell_layout`` over the tables that stack: the
    b*b per-block histograms for the vertical layouts (streamed or not, so
    both schedules pack the same rows), the b merged ones for horizontal.
    ``residency='disk'`` adds the shard-streaming I/O term
    (cost_model.disk_block_io_cost) to every non-skip block's cost and
    records e_cap so ``io_bytes_per_iter`` can model the per-iteration read
    volume.
    """
    assert mode in MODES, mode
    assert stream in STREAM_MODES, stream
    assert residency in RESIDENCY_MODES, residency
    if strategy == "horizontal" and merged_d_max is not None:
        # merged layout: a destination row's ELL slots merge ALL its source
        # blocks, so buckets size to the full per-row in-degree, not the
        # per-block maximum.
        assert merged_hist is not None
        d_max, hists = merged_d_max, np.asarray(merged_hist)
        unsplit = bucket_boundaries(d_max, max_buckets=max_buckets)
    else:
        hists, d_max, unsplit = ell_tables(
            recs, b=b, n_local=n_local, max_buckets=max_buckets,
            mxu_advantage=mxu_advantage)
    boundaries, split = ell_layout(hists, d_max, max_buckets=max_buckets)
    if not split:   # up to the d_max of every block, dense ones included
        boundaries = unsplit
    e_cap = max((r["nnz"] for r in recs), default=1)
    e_cap = max(e_cap, 1)
    io_cost = (cost_model.disk_block_io_cost(e_cap) if residency == "disk"
               else 0.0)
    blocks = tuple(
        _classify(recs[i * b + j], i, j, n_local, boundaries, mxu_advantage,
                  io_cost=io_cost, split=split)
        for i in range(b) for j in range(b))

    if scatter == "auto":
        # Gate the one-hot scatter-combine kernel on the measured crossover:
        # T = b*cap received slots, each either one serial segment write or
        # n_local+1 streamed one-hot slots.  Interpret mode's slot penalty
        # keeps the segment op on CPU hosts; plans without a compact
        # exchange (horizontal) never scatter.
        t = b * capacity if capacity is not None else 0
        scatter = ("kernel" if (mode == "planned" and capacity is not None and
                                cost_model.prefer_kernel_scatter(
                                    t, n_local + 1, interpret=interpret))
                   else "segment")
    return ExecutionPlan(
        strategy=strategy, mode=mode, b=b, n_local=n_local, theta=theta,
        capacity=capacity, boundaries=boundaries, blocks=blocks,
        scatter=scatter, stream=stream, residency=residency,
        e_cap=e_cap, split=split)


def format_plan(plan: ExecutionPlan, *, extra: dict | None = None) -> str:
    """Human-readable plan report (PMVEngine.explain)."""
    lines = [
        f"ExecutionPlan: strategy={plan.strategy} mode={plan.mode}"
        + (f" theta={plan.theta}" if plan.theta is not None else "")
        + (f" capacity={plan.capacity}" if plan.capacity is not None else "")
        + f" scatter={plan.scatter} stream={plan.stream}"
        + (f" residency={plan.residency}" if plan.residency != "device" else ""),
        f"  b={plan.b} n_local={plan.n_local} ell_buckets={plan.boundaries}",
    ]
    if plan.split:
        lines.append(f"  ell_cap={plan.ell_cap}: wider rows split into"
                     f" {plan.ell_cap}-wide rows that combine")
    if plan.residency == "disk":
        lines.append(
            f"  disk I/O: ~{plan.io_bytes_per_iter()} shard bytes/iter"
            f" (e_cap={plan.e_cap},"
            f" ~{cost_model.disk_io_seconds(plan.io_bytes_per_iter()) * 1e3:.2f}"
            " ms modeled)")
    for k, v in (extra or {}).items():
        lines.append(f"  {k}={v}")
    counts = plan.tactic_counts()
    lines.append("  tactics: " + " ".join(f"{t}={counts[t]}" for t in TACTICS))
    if plan.capacity is not None and plan.strategy != "horizontal":
        # only the vertical/hybrid compact path materializes partials —
        # horizontal (no partials, no capacity) has nothing to stream and
        # the ratio would be meaningless there.
        mp = plan.memory_profile()
        lines.append(
            f"  memory profile: materialized {mp['materialized_elems']} elems"
            f" -> streamed {mp['streamed_elems']} elems"
            f" ({mp['savings']:.2f}x) [stream={mp['stream']}]")
    flat, planned = plan.flat_padded_slots, plan.planned_slots
    if flat:
        lines.append(
            f"  ELL padded slots: flat {flat} -> planned {planned:.0f}"
            f" ({flat / max(planned, 1.0):.2f}x fewer)")
    hdr = f"  {'block':>8}  {'tactic':<6} {'nnz':>8} {'rows':>6} {'d_max':>6} {'occ':>6} {'cost':>10}"
    lines.append(hdr)
    for bp in plan.blocks:
        lines.append(
            f"  ({bp.i:>2},{bp.j:>2})  {bp.tactic:<6} {bp.nnz:>8} {bp.rows:>6}"
            f" {bp.d_max:>6} {bp.occupancy:>6.3f} {bp.cost:>10.0f}")
    return "\n".join(lines)
