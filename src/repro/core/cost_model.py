"""PMV I/O cost model (paper §3.4-3.5, Lemmas 3.1-3.3) + ICI adaptation.

The paper's costs count vector *elements* crossing distributed storage per
iteration; on a TPU pod the same counts, times bytes/element, cross the ICI.
The model drives three decisions, exactly as in the paper:

1. PMV_selective (Alg. 3): horizontal vs vertical via Eq. 5.
2. θ* for PMV_hybrid: argmin of Lemma 3.3 over candidate thresholds.
3. Capacity sizing of the compacted sparse exchange (expected partial size,
   Eq. 4 / Eq. 8, times a slack factor) — a TPU-only concern the paper's
   variable-size HDFS files didn't have.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.graph.stats import GraphStats

__all__ = [
    "horizontal_cost",
    "vertical_cost",
    "hybrid_cost",
    "expected_partial_nnz",
    "prefer_horizontal",
    "select_strategy",
    "theta_star",
    "ici_seconds",
    "HW",
    "materialized_partial_elems",
    "streamed_partial_elems",
    "prefer_streamed",
    "kernel_scatter_cost",
    "segment_scatter_cost",
    "prefer_kernel_scatter",
    "PACKED_ID_AMORTIZATION_ITERS",
    "padded_exchange_bytes",
    "packed_exchange_bytes",
    "prefer_packed_exchange",
    "SLOT_TIME_S",
    "slot_seconds",
    "RESIDENCY_MODES",
    "EDGE_SLOT_BYTES",
    "disk_block_io_cost",
    "disk_io_seconds",
    "per_host_io_seconds",
    "pipelined_iteration_seconds",
    "predicted_overlap",
    "stripe_slice_bytes",
    "prefer_disk_residency",
    "ELL_GATHER_SLOT_S",
    "ELL_SCATTER_ROW_S",
    "ell_layout_seconds",
]


# TPU v5e-class hardware constants (per chip), used for roofline + cost->time.
@dataclasses.dataclass(frozen=True)
class _HW:
    peak_flops_bf16: float = 197e12   # FLOP/s
    hbm_bw: float = 819e9             # B/s
    ici_link_bw: float = 50e9         # B/s per link
    ici_links: int = 4                # 2D torus: +/-x, +/-y


HW = _HW()


def _p_empty(b: int, n: int, m: int) -> float:
    """(1 - |M|/|v|^2)^(|v|/b): prob. a vertex has no in-edge from one block."""
    density = m / float(n) ** 2
    if density >= 1.0:
        return 0.0
    return float(np.exp((n / b) * np.log1p(-density)))


def horizontal_cost(b: int, n: int) -> float:
    """Lemma 3.1: E[C_h] = (b+1)|v|."""
    return (b + 1.0) * n


def expected_partial_nnz(b: int, n: int, m: int) -> float:
    """Eq. 4: E[|v^(i,j)|] = (|v|/b) (1 - (1-|M|/|v|^2)^(|v|/b))."""
    return (n / b) * (1.0 - _p_empty(b, n, m))


def vertical_cost(b: int, n: int, m: int) -> float:
    """Lemma 3.2: E[C_v] = 2|v| (1 + (b-1)(1 - (1-|M|/|v|^2)^(|v|/b)))."""
    return 2.0 * n * (1.0 + (b - 1.0) * (1.0 - _p_empty(b, n, m)))


def prefer_horizontal(b: int, n: int, m: int) -> bool:
    """Eq. 5: E[C_h] < E[C_v]  <=>  (1-|M|/|v|^2)^(|v|/b) < 0.5."""
    return _p_empty(b, n, m) < 0.5


def select_strategy(b: int, n: int, m: int) -> str:
    """PMV_selective (Alg. 3)."""
    return "horizontal" if prefer_horizontal(b, n, m) else "vertical"


def expected_sparse_partial_nnz(b: int, n: int, stats: GraphStats, theta: float) -> float:
    """Eq. 8: E[|v_s^(i,j)|] = (|v|/b) Σ_d (1 - (1 - P_out(θ)/b)^d) p_in(d)."""
    p_out = stats.p_out_below(theta)
    degs, p_in = stats.in_degree_hist()
    q = 1.0 - p_out / b
    term = float(np.sum((1.0 - np.power(q, degs)) * p_in))
    return (n / b) * term


def hybrid_cost(b: int, n: int, stats: GraphStats, theta: float) -> float:
    """Lemma 3.3 / Eq. 6:

    E[C_hb] = |v| (P_out(θ) + b (1 - P_out(θ)) + 1)
              + 2|v|(b-1) Σ_d (1 - (1 - P_out(θ)/b)^d) p_in(d)
    """
    return _hybrid_cost(b, n, stats.p_out_below(theta), *stats.in_degree_hist())


def _hybrid_cost(b: int, n: int, p_out: float, degs: np.ndarray,
                 p_in: np.ndarray) -> float:
    q = 1.0 - p_out / b
    tail = float(np.sum((1.0 - np.power(q, degs)) * p_in))
    return n * (p_out + b * (1.0 - p_out) + 1.0) + 2.0 * n * (b - 1.0) * tail


def theta_star(
    b: int, n: int, stats: GraphStats, candidates: np.ndarray | None = None
) -> tuple[float, float]:
    """argmin_θ E[C_hb] over candidate thresholds (paper §3.5: "compute the
    expected I/O cost of PMV_hybrid varying θ and choose the minimum").

    θ=0 degenerates to horizontal, θ=inf to vertical, so the search space
    always contains both basic methods -- hybrid can never be predicted worse.
    Returns (theta, expected_cost).
    """
    if candidates is None:
        uniq = stats.out_degree_values().astype(np.float64)
        # thresholds between observed degrees + the two degenerate endpoints
        candidates = np.unique(np.concatenate([[0.0], uniq, uniq + 1.0, [np.inf]]))
    # the in-degree histogram and the sorted out-degrees are shared by every
    # candidate: P_out(θ) = (# out-degrees < θ) / n, as stats.p_out_below.
    degs, p_in = stats.in_degree_hist()
    out_sorted = np.sort(stats.out_deg)
    best_theta, best_cost = 0.0, np.inf
    for theta in candidates:
        theta = float(theta)
        p_out = (1.0 if theta == np.inf else
                 float(np.searchsorted(out_sorted, theta, side="left")) / stats.n)
        cost = _hybrid_cost(b, n, p_out, degs, p_in)
        if cost < best_cost:
            best_theta, best_cost = theta, cost
    return best_theta, best_cost


def ici_seconds(elems: float, bytes_per_elem: int = 4, links: int | None = None) -> float:
    """Model time for moving `elems` vector elements across ICI per device."""
    links = HW.ici_links if links is None else links
    return elems * bytes_per_elem / (HW.ici_link_bw * links)


# ---------------------------------------------------------------------------
# Per-block tactic costs (planner.py): the planner compares, for each of the
# b x b pre-partitioned sub-blocks, the slots the ELL sparse kernel would
# touch against the MXU cost of materializing the block dense.
# ---------------------------------------------------------------------------

# One MXU dense slot costs ~1/8 of one gather/ELL slot: the systolic array
# streams 128x128 tiles at full clip while the sparse kernel pays the gather
# unit + padding per slot.  Calibrate on hardware; the ordering the planner
# needs (dense wins only on near-dense blocks) is insensitive to +-2x.
MXU_SLOT_ADVANTAGE = 8.0


# Modeled wall seconds per slot unit: one gather/ELL slot at HBM stream rate
# (8 B per slot / hbm_bw ~ 1e-11 s on a v5e chip; the interpret-mode hosts
# the tests run on land orders of magnitude above this).  This constant only
# anchors predicted_s in the obs layer's predicted-vs-measured report — the
# calibration residuals in BENCH_obs.json (repro.obs.report) are exactly the
# correction ROADMAP item 5 folds back in, so its absolute value is a
# starting point, not a claim.
SLOT_TIME_S = 1e-8


def slot_seconds(cost_slots: float) -> float:
    """Model time for ``cost_slots`` slot units of tactic compute (the
    predicted_s attached to launch spans by the obs layer)."""
    return cost_slots * SLOT_TIME_S


def ell_block_cost(bucketed_slots: int) -> float:
    """Per-iteration compute cost of an ell-tactic block = the padded slots
    its row-bucketed ELL slices touch (gather + combine per slot)."""
    return float(bucketed_slots)


def dense_block_cost(n_local: int, mxu_advantage: float = MXU_SLOT_ADVANTAGE) -> float:
    """Per-iteration compute cost of a dense-tactic block: the MXU streams
    all n_local^2 cells, each ~1/mxu_advantage of a gather slot."""
    return n_local * n_local / mxu_advantage


# Capped ELL width (planner.ell_layout): the source-value gather ahead of
# the ELL kernel costs the same for every packed slot, pad slots included,
# and every packed table row costs one scatter into the partial.  Read on one
# TPU v5e from device traces of the Graph500 scale-20 PageRank and WCC
# solves: each 1-D gather of 65,536 to 786,432 slots out of the
# VMEM-resident source vector took 7.13 ns per slot; the exchange's
# scatter-add combined 4,336,640 elements in 37.8 ms, ~8.7 ns per row.
ELL_GATHER_SLOT_S = 7.13e-9
ELL_SCATTER_ROW_S = 8.7e-9


def ell_layout_seconds(slots: float, rows: float) -> float:
    """Modelled per-sweep time of a packed ELL layout of ``slots`` table
    slots over ``rows`` table rows (pads included in both)."""
    return slots * ELL_GATHER_SLOT_S + rows * ELL_SCATTER_ROW_S


# ---------------------------------------------------------------------------
# Streamed vs materialized planned execution (planner.ExecutionPlan.stream).
#
# The paper's Alg. 2 never holds all b partial vectors v^(i,j) at once — each
# is stored to distributed storage as it is produced.  The planned executor
# can either materialize all partials before compaction (one fused launch per
# bucket, the fastest schedule when everything fits) or scan destination
# blocks and compact each partial immediately (O(n_local + b*cap) live
# memory, the paper's headline scalability property).  Streaming pays b
# sequential launch groups, so tiny b — where the materialized buffer is only
# a small multiple of the streamed one — keeps the fused fast path.
# ---------------------------------------------------------------------------

# Minimum live-memory reduction factor before the planner trades the fused
# launch schedule for the b-step streamed scan.
STREAM_MIN_SAVINGS = 2.0


def materialized_partial_elems(b: int, n_local: int) -> int:
    """Live partial-buffer elements (per worker) of the fused planned
    executor: all b destination-block partials at once."""
    return b * n_local


def streamed_partial_elems(b: int, n_local: int, capacity: int) -> int:
    """Live partial-buffer elements (per worker) of the bucket-streamed
    executor: one [n_local] partial in flight + the fixed [b, cap] compact
    exchange buffer."""
    return n_local + b * min(capacity, n_local)


def prefer_streamed(b: int, n_local: int, capacity: int) -> bool:
    """stream='auto' crossover: stream only when the materialized buffer is
    at least STREAM_MIN_SAVINGS x the streamed profile, so small-b solves
    keep the fused fast path and web-scale b gets Alg. 2's memory bound."""
    mat = materialized_partial_elems(b, n_local)
    return mat >= STREAM_MIN_SAVINGS * streamed_partial_elems(b, n_local, capacity)


# ---------------------------------------------------------------------------
# Receive-side scatter tactic (planner.ExecutionPlan.scatter).
#
# The Pallas scatter-combine kernel recasts the serial segment scatter as
# tiled one-hot reduction work: T received slots x n_out output rows on the
# MXU/VPU, vs T serial random-access writes for the XLA segment op.  The
# kernel's work grows with n_out while the segment op's does not, so the
# crossover is a pure n_out threshold (T divides out).  Interpret mode
# (CPU hosts) executes the tiles scalar-wise — the slot advantage becomes a
# penalty and the segment op always wins there.
# ---------------------------------------------------------------------------

# One serial random-access scatter write costs ~16 gather-slot units (read +
# write + address dependency stall), vs the MXU streaming n_out one-hot
# slots at 1/MXU_SLOT_ADVANTAGE each.  Calibrate on hardware like
# MXU_SLOT_ADVANTAGE; the crossover n_out = 16 * 8 = 128 only needs to be
# right within ~2x.
SERIAL_SCATTER_SLOT_COST = 16.0

# Interpret mode emulates the kernel's tiles with scalar host ops — the MXU
# advantage inverts into a large penalty, so the crossover never fires.
INTERPRET_SLOT_PENALTY = 64.0


def kernel_scatter_cost(t: float, n_out: int, *, interpret: bool = False,
                        mxu_advantage: float = MXU_SLOT_ADVANTAGE) -> float:
    """One-hot scatter-combine kernel cost: T x n_out slots on the MXU."""
    adv = mxu_advantage / INTERPRET_SLOT_PENALTY if interpret else mxu_advantage
    return t * n_out / adv


def segment_scatter_cost(t: float) -> float:
    """XLA segment-op cost: T serial random-access scatter writes."""
    return t * SERIAL_SCATTER_SLOT_COST


def prefer_kernel_scatter(t: float, n_out: int, *, interpret: bool = False) -> bool:
    """scatter='auto' crossover: take the one-hot kernel only while its
    T*n_out streamed work undercuts T serial scatter writes."""
    return kernel_scatter_cost(t, n_out, interpret=interpret) < segment_scatter_cost(t)


# ---------------------------------------------------------------------------
# Packed-exchange transport (repro.exchange; ROADMAP item 2).
#
# The compact sparse exchange re-ships an int32 index for every capacity slot
# every iteration; the packed exchange derives the per-(src, dst) index sets
# once at prepare() time (they are STATIC — the matrix structure never
# changes), ships the delta/bit-width-packed ids a single time, and streams
# only value payloads thereafter.  The comparison is therefore
#   padded:  b(b-1) * capacity * (4 + q*itemsize)          per iteration
#   packed:  payload_slots * q * itemsize                  per iteration
#            + id_bytes / PACKED_ID_AMORTIZATION_ITERS     (one-time, amortized)
# where payload_slots = Σ off-diagonal index-set sizes <= b(b-1) * capacity.
# ---------------------------------------------------------------------------

# Iterations the one-time id shipment is amortized over when comparing
# transports; typical PMV solves (PageRank/SSSP/CC to convergence) run well
# past this, so the gate is conservative — a solve that stops earlier still
# pays at most one padded-round-equivalent extra.
PACKED_ID_AMORTIZATION_ITERS = 10.0


def padded_exchange_bytes(b: int, capacity: int, nq: int | None,
                          itemsize: int) -> float:
    """Per-iteration wire bytes of the capacity-padded (idx, val) exchange —
    the byte model of sparse_exchange.exchange_wire_bytes, importable without
    jax for planning/explain."""
    return float(b * (b - 1) * capacity * (4 + (nq or 1) * itemsize))


def packed_exchange_bytes(payload_slots: int, nq: int | None,
                          itemsize: int) -> float:
    """Per-iteration wire bytes of the packed exchange's payload stream (the
    static ids ship once and are amortized separately)."""
    return float(payload_slots * (nq or 1) * itemsize)


def prefer_packed_exchange(
    b: int,
    capacity: int,
    payload_slots: int,
    id_bytes: int,
    nq: int | None,
    itemsize: int,
    *,
    amortization_iters: float = PACKED_ID_AMORTIZATION_ITERS,
) -> bool:
    """exchange='auto' gate: take the packed transport when its amortized
    per-iteration bytes undercut the padded stream's."""
    padded = padded_exchange_bytes(b, capacity, nq, itemsize)
    packed = (packed_exchange_bytes(payload_slots, nq, itemsize)
              + id_bytes / amortization_iters)
    return packed < padded


# ---------------------------------------------------------------------------
# Disk-residency I/O leg (paper §3.4: PMV's costs were *disk* I/O counts in
# the original system; the TPU adaptation re-grows that leg for the
# out-of-core block store, repro.store).  residency='disk' keeps the
# pre-partitioned shards on disk and streams one destination block's slices
# per launch-schedule step, so every non-skip block pays a sequential read of
# its padded e_cap slots on top of its compute tactic.
# ---------------------------------------------------------------------------

RESIDENCY_MODES = ("device", "host", "disk")

# Bytes per padded edge slot in a shard slice: int32 seg + int32 gat + f32 w.
EDGE_SLOT_BYTES = 12

# Modeled sequential-read bandwidth for the shard memmaps (NVMe-class).
# Like MXU_SLOT_ADVANTAGE this is a calibrate-on-hardware constant; the
# planner only needs the ordering (disk slots are far slower than gather
# slots) to be right within ~2x.
DISK_READ_BW = 2e9  # B/s

# One gather/ELL compute slot expressed in disk bytes: with double-buffered
# prefetch the read overlaps compute, so the planner charges the *excess*
# of I/O over compute per block; 32 streamed bytes per slot-unit keeps small
# blocks I/O-bound and dense blocks compute-bound, matching the measured
# shapes in the store bench.
DISK_SLOT_BYTES_EQUIV = 32.0


def _slot_bytes(has_w: bool) -> int:
    """Bytes per padded edge slot: the full EDGE_SLOT_BYTES when the f32
    weight array is materialized, the int32 seg+gat pair otherwise (shards
    never store weights — they are recomputed host-side)."""
    return EDGE_SLOT_BYTES if has_w else EDGE_SLOT_BYTES - 4


def stripe_slice_bytes(workers: int, e_cap: int, *, has_w: bool = False) -> int:
    """Bytes of ONE destination (or source) block's shard slice across all
    workers: [workers, e_cap] seg + gat plus the counts.  ``has_w=True``
    adds the recomputed f32 weight array — RESIDENT bytes (the budget
    metric), not disk-read bytes."""
    return workers * (e_cap * _slot_bytes(has_w) + 4)


def disk_block_io_cost(e_cap: int, *, has_w: bool = False) -> float:
    """Per-iteration slot-unit cost of streaming one block's shard slice
    from disk (the I/O term added to every non-skip tactic cost when
    residency='disk').  Weights are recomputed host-side, never read, so
    the default charges only the seg+gat stream."""
    return e_cap * _slot_bytes(has_w) / DISK_SLOT_BYTES_EQUIV


def disk_io_seconds(bytes_read: float) -> float:
    """Model time for streaming ``bytes_read`` shard bytes from disk."""
    return bytes_read / DISK_READ_BW


def per_host_io_seconds(bytes_read: float, workers: int) -> float:
    """Model time for the SPMD disk leg: ``bytes_read`` TOTAL shard bytes
    split across ``workers`` hosts, each streaming its own stripe range
    from its own disk concurrently — the critical path is one host's
    share, which is how the multi-host engine scales the paper's I/O
    term."""
    if workers <= 0:
        raise ValueError(f"workers must be positive, got {workers}")
    return disk_io_seconds(bytes_read / workers)


def pipelined_iteration_seconds(io_s: float, wire_s: float,
                                compute_s: float) -> float:
    """Predicted wall time of one pipelined out-of-core iteration: the
    prefetch pipeline overlaps disk I/O with exchange + compute (fetch of
    block k+1 behind compute of k, and iteration t+1's first fetch behind
    t's tail), so the iteration costs the MAX of the legs plus the
    un-overlappable pipeline fill (one block's fetch ~ io_s spread over
    the schedule, charged as the non-critical legs' startup)."""
    return max(io_s, wire_s + compute_s)


def predicted_overlap(io_s: float, wire_s: float, compute_s: float) -> float:
    """Fraction of disk time the pipeline is predicted to hide (the model
    counterpart of ``ResidencyStats.overlap``): compute+wire time covers
    that much of the I/O leg."""
    if io_s <= 0.0:
        return 1.0
    return max(0.0, min(1.0, (wire_s + compute_s) / io_s))


def prefer_disk_residency(shard_bytes: int, budget_bytes: int | None) -> bool:
    """residency='auto' helper: spill to disk only when the resident block
    set does not fit the configured budget (no budget -> keep in memory)."""
    return budget_bytes is not None and shard_bytes > budget_bytes


def capacity_from_cost_model(
    b: int,
    n: int,
    m: int,
    *,
    stats: GraphStats | None = None,
    theta: float | None = None,
    slack: float = 1.5,
) -> int:
    """Cost-model capacity for the compacted exchange (Eq. 4 or Eq. 8 x slack)."""
    if theta is not None and stats is not None:
        exp = expected_sparse_partial_nnz(b, n, stats, theta)
    else:
        exp = expected_partial_nnz(b, n, m)
    return max(1, int(np.ceil(exp * slack)))
