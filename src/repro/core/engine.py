"""PMVEngine: pre-partition once, iterate M (x) v to convergence (paper §3.1).

Two execution modes share the same placement code (placement.py):

- emulation (mesh=None): all b workers' shards live on one device with an
  explicit leading worker axis; collectives are jnp reshapes.  This is what
  CPU tests and the paper-figure benchmarks run.
- SPMD (mesh given): `shard_map` over the 'workers' axis; collectives are
  real `jax.lax` ops.  The dry-run lowers this mode for the production mesh.

Per-iteration the engine reports both *physical* communicated elements (the
static buffers that actually cross ICI) and *logical* elements (value-level
non-identity entries — the paper's I/O metric), so the benchmark figures can
be compared against the paper's Figures 5/6 directly.
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import blocks as blocks_lib
from repro.core import cost_model, placement, planner, sparse_exchange
from repro.core.blocks import BlockEdges, DenseRegion
from repro.core.mesh import as_auto_mesh
from repro.exchange import plan as exchange_plan
from repro.kernels.block_gimv import has_semiring, semiring_of
from repro.core.gimv import GimvSpec
from repro.core.partition import HybridMatrix, Partition, PartitionedMatrix, partition_graph
from repro.graph.generators import symmetrize_edges
from repro.faults import as_injector
from repro.obs import as_recorder

__all__ = ["PMVEngine", "PMVResult", "StepConfig", "make_step", "placement_call"]


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """Static per-step configuration, derived from the ExecutionPlan.

    ``backend`` is the resolved execution mode ('xla' | 'pallas' |
    'planned'); ``plan`` carries the full per-block tactic table that the
    'planned' mode executes and ``explain()`` reports.  The config is frozen
    and hashable so jitted steps can close over it."""

    strategy: str            # 'horizontal' | 'vertical' | 'hybrid'
    n_local: int
    exchange: str = "sparse"  # resolved transport: 'sparse'|'dense'|'hier'|'packed'
    capacity: int | None = None
    payload_dtype: str | None = None  # e.g. 'bfloat16' wire values (§Perf)
    backend: str = "xla"     # resolved mode: 'xla' | 'pallas' | 'planned'
    interpret: bool = False  # Pallas interpret mode (CPU hosts / debugging)
    stream: str = "off"      # resolved partial schedule: 'on' | 'off'
    plan: planner.ExecutionPlan | None = None
    # packed exchange (repro.exchange): the static byte-model plan (frozen,
    # hashable) and the resolved delta-iteration threshold (None = full
    # stream; set only when the semiring admits suppression — see prepare).
    xplan: exchange_plan.ExchangePlan | None = None
    delta_eps: float | None = None


def _stack_stripes(stripes: list[BlockEdges]) -> BlockEdges:
    """b per-worker stripes -> arrays with a leading worker axis."""
    return jax.tree.map(lambda *xs: np.stack(xs, axis=0), *stripes)


def _squeeze0(tree):
    return jax.tree.map(lambda a: a[0], tree)


def placement_call(spec: GimvSpec, cfg: StepConfig, matrix, v, ctx, mask, axis,
                   xstate=None):
    """Dispatch one placement step for ``cfg.strategy``.

    Shared by the engine's scalar step and repro.serving's multi-query step
    (v/ctx may carry a trailing query axis; placements are polymorphic).
    Returns (v_new, r, stats) — plus the new delta-iteration state as a
    fourth element when ``xstate`` (the previously-shipped packed payload)
    is passed."""
    n_local = cfg.n_local
    scatter = cfg.plan.scatter if cfg.plan is not None else "segment"
    if cfg.strategy == "horizontal":
        return placement.horizontal_step(
            spec, matrix["stripe"], v, ctx, mask, n_local=n_local, axis_name=axis,
            ell=matrix.get("ell"), planned=matrix.get("planned"),
            backend=cfg.backend, interpret=cfg.interpret)
    if cfg.strategy == "vertical":
        pd = jnp.dtype(cfg.payload_dtype) if cfg.payload_dtype else None
        return placement.vertical_step(
            spec, matrix["stripe"], v, ctx, mask, n_local=n_local, axis_name=axis,
            exchange=cfg.exchange, capacity=cfg.capacity, payload_dtype=pd,
            ell=matrix.get("ell"), planned=matrix.get("planned"),
            streamed=matrix.get("streamed"),
            xchg=matrix.get("xchg"), xplan=cfg.xplan,
            delta_eps=cfg.delta_eps, delta_state=xstate,
            backend=cfg.backend, scatter=scatter, interpret=cfg.interpret)
    if cfg.strategy == "hybrid":
        pd = jnp.dtype(cfg.payload_dtype) if cfg.payload_dtype else None
        return placement.hybrid_step(
            spec, matrix["sparse_stripe"], matrix["dense_stripe"], matrix["dense_region"],
            v, ctx, mask, n_local=n_local, axis_name=axis, capacity=cfg.capacity,
            exchange=cfg.exchange,
            payload_dtype=pd, sparse_ell=matrix.get("sparse_ell"),
            planned_sparse=matrix.get("planned_sparse"),
            streamed_sparse=matrix.get("streamed_sparse"),
            xchg=matrix.get("xchg"), xplan=cfg.xplan,
            dense_matrix=matrix.get("dense_matrix"), backend=cfg.backend,
            scatter=scatter, interpret=cfg.interpret)
    raise ValueError(cfg.strategy)


def make_step(spec: GimvSpec, cfg: StepConfig, mesh: Mesh | None = None, axis_name: str = "workers"):
    """Build step(matrix, v, ctx, mask) -> (v_new, delta, stats).

    matrix: dict pytree of stripe / dense-region arrays, leading worker axis.
    v/ctx/mask: blocked [b, n_local] arrays.  In SPMD mode everything is
    sharded on the worker axis and the function is shard_map'ped; delta and
    stats come out replicated.
    """

    def _placement_call(matrix, v, ctx, mask, axis, xstate=None):
        return placement_call(spec, cfg, matrix, v, ctx, mask, axis, xstate)

    with_state = cfg.delta_eps is not None

    if mesh is None:
        if with_state:
            def step(matrix, v, ctx, mask, xstate):
                v_new, _r, stats, xnew = _placement_call(
                    matrix, v, ctx, mask, None, xstate)
                delta = spec.default_delta(v, v_new)
                return v_new, delta, stats, xnew
            return step

        def step(matrix, v, ctx, mask):
            v_new, _r, stats = _placement_call(matrix, v, ctx, mask, None)
            delta = spec.default_delta(v, v_new)
            return v_new, delta, stats
        return step

    sharded = P(axis_name)
    repl = P()
    if with_state:
        def body_state(matrix, v, ctx, mask, xstate):
            matrix, v, ctx, mask, xstate = (
                _squeeze0(t) for t in (matrix, v, ctx, mask, xstate))
            v_new, _r, stats, xnew = _placement_call(
                matrix, v, ctx, mask, axis_name, xstate)
            delta = jax.lax.psum(spec.default_delta(v, v_new), axis_name)
            stats = {k: (s if s.ndim == 0 else s) for k, s in stats.items()}
            return v_new[None], delta, stats, xnew[None]

        return jax.shard_map(
            body_state,
            mesh=mesh,
            in_specs=(sharded, sharded, sharded, sharded, sharded),
            out_specs=(sharded, repl, repl, sharded),
            check_vma=False,
        )

    def body(matrix, v, ctx, mask):
        matrix, v, ctx, mask = (_squeeze0(t) for t in (matrix, v, ctx, mask))
        v_new, _r, stats = _placement_call(matrix, v, ctx, mask, axis_name)
        delta = jax.lax.psum(spec.default_delta(v, v_new), axis_name)
        stats = {k: (s if s.ndim == 0 else s) for k, s in stats.items()}
        return v_new[None], delta, stats

    step = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded),
        out_specs=(sharded, repl, repl),
        check_vma=False,
    )
    return step


@dataclasses.dataclass
class PMVResult:
    v: np.ndarray
    iterations: int
    converged: bool
    strategy: str
    theta: float | None
    capacity: int | None
    per_iter: list[dict]
    totals: dict

    @property
    def physical_elems_per_iter(self) -> float:
        if not self.per_iter:
            return 0.0
        last = self.per_iter[-1]
        return float(last.get("gathered_elems", 0.0) + last.get("exchanged_elems", 0.0))

    @property
    def deltas(self) -> np.ndarray:
        """Per-iteration convergence-delta trajectory (convergence curves
        without a rerun)."""
        return np.asarray([r["delta"] for r in self.per_iter])


class PMVEngine:
    """Scalable GIM-V engine with pre-partitioning + placement selection.

    strategy: 'horizontal' | 'vertical' | 'selective' (Eq. 5 auto-pick
      between the two basics) | 'hybrid' (θ-split, the paper's best).
    theta: float or 'auto' (= θ* argmin of Lemma 3.3).
    exchange: 'sparse' (compacted, paper-faithful) | 'dense' (all_to_all the
      full partial vectors — the strawman dense-collective schedule) |
      'packed' (repro.exchange: per-(src,dst) index sets derived once at
      prepare() time, ids shipped a single time delta/bit-width packed, each
      iteration streams only value payloads in that fixed order — bitwise
      the sparse exchange, overflow-free by construction) | 'auto' (packed
      when cost_model.prefer_packed_exchange says its amortized bytes
      undercut the padded stream, else sparse).
    capacity: 'structural' (exact max partial nnz — overflow-free) |
      'model' (Eq. 4/8 x slack — tighter, may overflow -> engine retries
      with the dense exchange for that run).
    payload_dtype: wire dtype for the sparse-exchange values (e.g.
      'bfloat16' — §Perf); accumulation stays in the spec dtype.
    delta_eps: convergence-driven delta iteration over the packed exchange
      (vertical, in-memory): carry the previously-shipped payload and
      re-send only rows that moved > delta_eps since the last send
      (delta_eps=0.0 re-sends on any bitwise change — exact).  Enabled only
      for combineAll='sum' semirings over floating payloads (PageRank/RWR
      style), where an eps-stale value perturbs the sum by at most eps per
      suppressed row; exact-selection semirings (min/max combineAll) keep
      the full stream — their results must never carry approximation — and
      explain() reports why.
    backend: 'auto' engages the per-block execution planner (core/planner.py):
      every b x b sub-block is classified at prepare() time into skip / ell
      (row-bucketed ELL slices) / dense (MXU matmul) tactics by density, and
      the step executes the resulting ExecutionPlan with fused same-tactic
      launches.  'xla' (generic gather/segment lowering) and 'pallas' (the
      flat global kernel layout) remain as forced overrides.  Specs whose
      (combine2, combineAll) pair has no kernel semiring fall back to 'xla'
      (recorded in meta['backend']); every prepared solve carries its plan in
      meta['plan'] and pretty-prints it via ``explain()``.
    scatter: receive-side tactic of the sparse exchange — 'segment' (XLA
      segment op), 'kernel' (Pallas scatter-combine kernel), or 'auto'
      (gated on the cost model's T*n_out-vs-serial-scatter crossover,
      cost_model.prefer_kernel_scatter; interpret mode's slot penalty keeps
      the segment op on CPU hosts).
    stream: partial-vector schedule of the planned vertical/hybrid compact
      path — 'off' materializes all b destination-block partials before
      compaction (fused same-tactic launches), 'on' scans destination blocks
      and compacts each partial immediately (paper Alg. 2's
      O(n_local + b*cap) live memory, bitwise identical results), 'auto'
      picks by the cost model's memory crossover (cost_model.prefer_streamed
      — tiny b keeps the fused fast path).  Applies to planned mode with a
      compact exchange; the forced 'xla'/'pallas' backends already stream
      (their scan paths), and the dense exchange ships full partials.
    pallas_interpret: force the kernels' interpret mode; default None runs
      interpret on non-TPU hosts and compiled kernels on TPU.
    store / residency: run against an out-of-core pre-partitioned block
      store (repro.store) instead of an in-memory edge list.  ``store`` is a
      store directory path or Manifest; ``residency`` picks the matrix home:
      'host'/'device' load the shards back (bitwise partition_graph) and run
      the classic paths; 'disk' never materializes the stripes — the solve
      walks the plan's launch schedule, fetching one block's shard slice at
      a time with double-buffered prefetch (store/residency.py).  Vertical
      disk execution is bitwise the resident vertical step.
      ``store_budget_bytes`` bounds the resident slice bytes in 'disk' mode.
    """

    def __init__(
        self,
        edges: np.ndarray | None,
        n: int | None = None,
        *,
        b: int | None = None,
        strategy: str = "selective",
        theta: float | str = "auto",
        psi: str | None = None,
        exchange: str = "sparse",
        capacity: str = "structural",
        slack: float = 1.5,
        payload_dtype: str | None = None,
        delta_eps: float | None = None,
        backend: str = "xla",
        scatter: str = "auto",
        stream: str = "auto",
        pallas_interpret: bool | None = None,
        symmetrize: bool = False,
        base_weights: np.ndarray | None = None,
        mesh: Mesh | None = None,
        axis_name: str = "workers",
        store=None,
        residency: str = "device",
        store_budget_bytes: int | None = None,
        obs=None,
        faults=None,
        io_retry=None,
    ):
        # psi=None means "unspecified": 'cyclic' without a store, the
        # manifest's ψ with one — an EXPLICIT psi must match the store.
        assert backend in ("xla", "pallas", "auto"), backend
        assert scatter in ("auto",) + sparse_exchange.SCATTER_METHODS, scatter
        assert stream in ("auto",) + planner.STREAM_MODES, stream
        assert residency in cost_model.RESIDENCY_MODES, residency
        self.store = None
        self.residency = residency
        self.store_budget_bytes = store_budget_bytes
        if store is not None:
            from repro.store import open_store

            self.store = open_store(store)
            if edges is not None:
                raise ValueError("pass either edges or store=, not both")
            if n is not None and int(n) != self.store.n:
                raise ValueError(f"n={n} does not match the store's n={self.store.n}")
            if b is not None and int(b) != self.store.b:
                raise ValueError(f"b={b} does not match the store's b={self.store.b}")
            if psi is not None and psi != self.store.psi:
                raise ValueError(
                    f"psi={psi!r} does not match the store's psi={self.store.psi!r}")
            psi = self.store.psi
            if symmetrize and not self.store.symmetrized:
                raise ValueError(
                    "symmetrize=True but the store was ingested without "
                    "symmetrize — re-ingest with ingest_edges(symmetrize=True)")
            if base_weights is not None:
                raise ValueError("base_weights are not persisted by the store")
            n, b = self.store.n, self.store.b
            edges = None
        else:
            if edges is None or n is None or b is None:
                raise ValueError("PMVEngine needs (edges, n, b=) or store=")
            if residency != "device":
                raise ValueError(
                    f"residency={residency!r} needs store= (an ingested "
                    "block-store directory; see repro.store.ingest_edges)")
            if symmetrize:
                edges = symmetrize_edges(edges)
            edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
            psi = psi or "cyclic"
        self.edges = edges
        self.n = int(n)
        self.b = int(b)
        self.strategy = strategy
        self.theta = theta
        self.psi = psi
        assert exchange in ("sparse", "dense", "hier", "packed", "auto"), exchange
        assert delta_eps is None or delta_eps >= 0.0, delta_eps
        self.exchange = exchange
        self.capacity_mode = capacity
        self.slack = slack
        self.payload_dtype = payload_dtype
        self.delta_eps = delta_eps
        self.backend = backend
        self.scatter = scatter
        self.stream = stream
        self.pallas_interpret = pallas_interpret
        self.base_weights = base_weights
        self.mesh = as_auto_mesh(mesh)
        self.axis_name = axis_name
        # obs: None/False (the zero-overhead null recorder), True (a fresh
        # repro.obs.Recorder), or a Recorder shared with a server / store.
        self.obs = as_recorder(obs)
        # faults: None (hot path untouched), a seeded repro.faults.FaultPlan,
        # or a live FaultInjector shared with a store / a resumed run — the
        # injector's consumed-event state survives a kill-and-resume, so a
        # kill fired in run #1 does not re-fire on resume.  io_retry bounds
        # every disk fetch (repro.faults.RetryPolicy; None = default policy).
        self._fault_injector = as_injector(faults, self.obs)
        self.io_retry = io_retry
        self._prep_cache: dict = {}  # spec -> (step, matrix, mask, meta); FIFO-bounded

    _PREP_CACHE_MAX = 8

    # ------------------------------------------------------------------
    @classmethod
    def from_store(cls, store, **kwargs) -> "PMVEngine":
        """Engine over an ingested block store (path or Manifest); n/b/psi
        come from the manifest.  ``residency`` defaults to 'host'."""
        kwargs.setdefault("residency", "host")
        return cls(None, store=store, **kwargs)

    def _num_edges(self) -> int:
        return self.store.m if self.store is not None else self.edges.shape[0]

    def _graph_stats(self):
        if self.store is not None:
            return self.store.graph_stats()
        from repro.graph.stats import compute_stats
        return compute_stats(self.edges, self.n)

    def resolve_strategy(self) -> tuple[str, float | None]:
        m = self._num_edges()
        if self.strategy in ("horizontal", "vertical"):
            return self.strategy, None
        if self.strategy in ("auto", "selective"):
            return cost_model.select_strategy(self.b, self.n, m), None
        if self.strategy == "hybrid":
            if self.theta == "auto":
                theta, _ = cost_model.theta_star(self.b, self.n, self._graph_stats())
            else:
                theta = float(self.theta)
            return "hybrid", theta
        raise ValueError(self.strategy)

    def prepare(self, spec: GimvSpec, ctx: dict | None = None):
        """Pre-partitioning (runs once; paper §3.1.1): builds device-resident
        matrix stripes, the blocked initial vector, and the jitted step.

        The expensive parts (partitioning, device placement, the jitted step)
        are cached per ``spec`` instance, so repeated ``run`` calls — e.g. a
        serving loop answering many queries against one graph — pay the
        partition + compile cost once.  Only v0 / ctx are rebuilt per call.
        """
        if spec not in self._prep_cache:
            self._prep_cache[spec] = self._prepare_static(spec)
            while len(self._prep_cache) > self._PREP_CACHE_MAX:  # bound device residency
                self._prep_cache.pop(next(iter(self._prep_cache)))
        step_jit, matrix, real_mask_dev, meta = self._prep_cache[spec]
        part = meta["part"]

        ids = part.global_ids_grid()            # [b, n_local]
        ctx = ctx or {}
        v0 = spec.init(ids.reshape(-1), ctx).reshape(ids.shape).astype(spec.dtype)
        ctx_blocked = {k: part.to_blocked(np.asarray(x)) for k, x in ctx.items()}
        if self.mesh is not None:
            shard = NamedSharding(self.mesh, P(self.axis_name))
            v0 = jax.device_put(jnp.asarray(v0), shard)
            ctx_blocked = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), shard), ctx_blocked)
        else:
            v0 = jnp.asarray(v0)
            ctx_blocked = jax.tree.map(jnp.asarray, ctx_blocked)
        return step_jit, matrix, v0, ctx_blocked, real_mask_dev, meta

    def _prepare_static(self, spec: GimvSpec):
        """Partition + device matrix + jitted step (the per-spec cacheable part)."""
        strategy, theta = self.resolve_strategy()
        if self.store is not None and self.residency == "disk":
            return self._prepare_disk(spec, strategy, theta)
        rec = self.obs
        with rec.span("prepare.partition") as sp:
            sp.set("spec", spec.name)
            sp.set("strategy", strategy)
            if self.store is not None:
                from repro.store import load_partitioned

                pm, hm = load_partitioned(
                    self.store, spec,
                    theta=theta if strategy == "hybrid" else None)
            else:
                pm, hm = partition_graph(
                    self.edges, self.n, self.b, spec,
                    psi=self.psi, base_weights=self.base_weights,
                    theta=theta if strategy == "hybrid" else None,
                )
        part = pm.part

        backend = self._resolve_backend(spec)
        interpret = (jax.default_backend() != "tpu"
                     if self.pallas_interpret is None else self.pallas_interpret)

        stripes_span = rec.span("prepare.stripes")
        stripes_span.__enter__()
        if strategy == "horizontal":
            matrix = {"stripe": _stack_stripes(pm.horizontal)}
            capacity = None
            if backend == "pallas":
                # merged ELL: cols pre-offset into the flat gathered vector
                matrix["ell"] = blocks_lib.stack_ells([
                    blocks_lib.stripe_to_ell(s, part.n_local, merge_col_stride=part.n_local)
                    for s in pm.horizontal])
        elif strategy == "vertical":
            matrix = {"stripe": _stack_stripes(pm.vertical)}
            capacity = self._capacity(pm, None)
            if backend == "pallas":
                # per-destination-block ELL for the streamed compact scan
                matrix["ell"] = blocks_lib.stack_ells([
                    blocks_lib.stripe_to_ell(s, part.n_local) for s in pm.vertical])
        else:
            assert hm is not None
            matrix = {
                "sparse_stripe": _stack_stripes(hm.sparse_vertical),
                "dense_stripe": _stack_stripes(hm.dense_horizontal),
                "dense_region": DenseRegion(
                    gather_idx=hm.dense.gather_idx,
                    d_count=hm.dense.d_count,
                    d_cap=hm.dense.d_cap,
                    theta=hm.dense.theta,
                ),
            }
            capacity = self._capacity(pm, hm)
            if backend == "pallas" or (backend == "planned" and hm.dense_nnz > 0):
                semiring = semiring_of(spec.combine2, spec.combine_all)
                if backend == "pallas":
                    matrix["sparse_ell"] = blocks_lib.stack_ells([
                        blocks_lib.stripe_to_ell(s, part.n_local) for s in hm.sparse_vertical])
                # the dense REGION is a region-level dense tactic (§3.5):
                # both kernel modes run it as a materialized MXU matmul.  The
                # planned mode skips an empty region (θ above every
                # out-degree), whose stripes hold no edge to run.
                matrix["dense_matrix"] = np.stack([
                    blocks_lib.materialize_dense_matrix(
                        s, part.n_local, hm.dense.d_cap, semiring)
                    for s in hm.dense_horizontal])

        stripes_span.__exit__(None, None, None)
        # the scatter-combine kernel shares the semiring table: a spec with
        # no kernel semiring degrades a forced 'kernel' to the segment op,
        # mirroring the backend fallback.
        scatter = (self.scatter
                   if has_semiring(spec.combine2, spec.combine_all) else "segment")
        stream = self._resolve_stream(strategy, backend, capacity, part)
        with rec.span("prepare.plan") as sp:
            plan = planner.plan_execution(
                pm, hm, strategy=strategy, mode=backend, theta=theta,
                capacity=capacity, scatter=scatter, stream=stream,
                interpret=interpret, residency=self.residency)
            sp.set("mode", backend)
            sp.set("predicted_slots", plan.planned_slots)
        self._record_plan_metrics(plan)
        pack_span = rec.span("prepare.pack")
        pack_span.__enter__()
        if backend == "planned":
            semiring = semiring_of(spec.combine2, spec.combine_all)
            # emulation packs the streamed layout scan-major so the executor's
            # lax.scan over destination blocks never transposes the tables;
            # SPMD keeps the worker axis leading for shard_map to split.
            w_axis = 0 if self.mesh is not None else 1

            def _pack_vertical(stripes):
                if stream == "on":
                    return "streamed", blocks_lib.stack_streamed([
                        blocks_lib.pack_streamed_stripe(
                            s, plan.tactics_for_worker(j, "vertical"), part.n_local,
                            boundaries=plan.boundaries, semiring=semiring)
                        for j, s in enumerate(stripes)], semiring, worker_axis=w_axis)
                return "planned", blocks_lib.stack_planned([
                    blocks_lib.pack_planned_stripe(
                        s, plan.tactics_for_worker(j, "vertical"), part.n_local,
                        layout="vertical", boundaries=plan.boundaries, semiring=semiring)
                    for j, s in enumerate(stripes)], semiring)

            if strategy == "horizontal":
                matrix["planned"] = blocks_lib.stack_planned([
                    blocks_lib.pack_planned_stripe(
                        s, plan.tactics_for_worker(i, "merged"), part.n_local,
                        layout="merged", boundaries=plan.boundaries, semiring=semiring)
                    for i, s in enumerate(pm.horizontal)], semiring)
            elif strategy == "vertical":
                key, packed = _pack_vertical(pm.vertical)
                matrix[key] = packed
            else:
                key, packed = _pack_vertical(hm.sparse_vertical)
                matrix[key + "_sparse"] = packed

        pack_span.__exit__(None, None, None)
        real_mask = part.global_ids_grid() < self.n

        # -- exchange transport resolution: build the packed index sets when
        # requested (or when 'auto' should weigh them against the padded
        # stream), and gate delta iteration on semiring soundness.
        exchange, xplan, delta_eps, xmeta = self._resolve_exchange(
            spec, strategy, capacity, plan,
            pm.vertical if strategy == "vertical" else
            (hm.sparse_vertical if hm is not None else None),
            part, matrix)

        cfg = StepConfig(strategy=strategy, n_local=part.n_local,
                         exchange=exchange, capacity=capacity,
                         payload_dtype=self.payload_dtype,
                         backend=backend, interpret=interpret, stream=stream,
                         plan=plan, xplan=xplan, delta_eps=delta_eps)
        step = make_step(spec, cfg, self.mesh, self.axis_name)
        donate = (1, 4) if delta_eps is not None else (1,)
        step_jit = jax.jit(step, donate_argnums=donate)

        device_span = rec.span("prepare.device_put")
        device_span.__enter__()
        if self.mesh is not None:
            if self.residency == "host":
                raise NotImplementedError(
                    "residency='host' under SPMD needs per-host shard "
                    "serving; use residency='device' with a mesh")
            shard = NamedSharding(self.mesh, P(self.axis_name))
            matrix = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), shard), matrix)
            real_mask_dev = jax.device_put(jnp.asarray(real_mask), shard)
        elif self.residency == "host":
            # host residency: stripes stay as host numpy — the jitted step
            # transfers them per call (HBM is never committed to the full
            # block set; on CPU hosts the transfer is a no-op).
            matrix = jax.tree.map(np.asarray, matrix)
            real_mask_dev = jnp.asarray(real_mask)
        else:
            matrix = jax.tree.map(jnp.asarray, matrix)
            real_mask_dev = jnp.asarray(real_mask)
        device_span.__exit__(None, None, None)

        meta = {
            "strategy": strategy, "theta": theta, "capacity": capacity,
            "part": part, "pm": pm, "hm": hm, "cfg": cfg, "backend": backend,
            "plan": plan, "residency": self.residency,
            "n_dense": int(hm.dense.d_count.sum()) if hm is not None else 0,
            **xmeta,
        }
        return step_jit, matrix, real_mask_dev, meta

    def _wire_itemsize(self, spec: GimvSpec) -> int:
        return jnp.dtype(self.payload_dtype or spec.dtype).itemsize

    def _resolve_exchange(self, spec: GimvSpec, strategy: str,
                          capacity: int | None, plan, stripes, part, matrix):
        """Resolve self.exchange ('auto' weighs packed vs padded via the cost
        model) and, for 'packed', derive the static index sets from the block
        structure, stash the device arrays in the matrix pytree, and gate
        delta iteration.  Returns (exchange, xplan, delta_eps, meta_extra)."""
        exchange = self.exchange
        xplan = None
        delta_eps = None
        decision = "forced"
        if strategy == "horizontal" or stripes is None or capacity is None:
            if exchange in ("packed", "auto"):
                exchange = "sparse"  # no partial exchange to pack
            return exchange, None, None, {"exchange": exchange,
                                          "exchange_decision": "n/a"}
        if exchange in ("packed", "auto"):
            with self.obs.span("prepare.exchange") as sp:
                row_sets = exchange_plan.row_sets_from_stripes(stripes, self.b)
                xp, arrays = exchange_plan.build_exchange(
                    row_sets, part.n_local, scatter=plan.scatter)
                sp.set("p_cap", xp.p_cap)
                sp.set("id_bytes", xp.id_bytes)
            if exchange == "auto":
                use_packed = cost_model.prefer_packed_exchange(
                    self.b, capacity, xp.payload_slots, xp.id_bytes,
                    None, self._wire_itemsize(spec))
                exchange = "packed" if use_packed else "sparse"
                decision = ("auto: packed undercuts padded" if use_packed
                            else "auto: padded stream kept")
            if exchange == "packed":
                matrix["xchg"] = {k: np.asarray(v) for k, v in arrays.items()}
                xplan = xp
        delta_reason = None
        if self.delta_eps is not None:
            wire_dt = jnp.dtype(self.payload_dtype or spec.dtype)
            if exchange != "packed":
                delta_reason = "needs exchange='packed'"
            elif strategy != "vertical":
                delta_reason = "vertical-only (hybrid keeps the full stream)"
            elif spec.combine_all != "sum":
                delta_reason = (f"combineAll={spec.combine_all!r} is exact "
                                "selection — full stream kept")
            elif not jnp.issubdtype(wire_dt, jnp.floating):
                delta_reason = "integer payloads keep the full stream"
            else:
                delta_eps = float(self.delta_eps)
                delta_reason = "active"
        return exchange, xplan, delta_eps, {
            "exchange": exchange, "exchange_decision": decision,
            "delta_eps": delta_eps, "delta_reason": delta_reason,
        }

    def _record_plan_metrics(self, plan: planner.ExecutionPlan) -> None:
        """Plan-shape gauges: tactic mix, padding occupancy, predicted cost
        (prepare-time; one write per gauge, nothing on the hot path)."""
        rec = self.obs
        if not rec.enabled:
            return
        rec.gauge("plan.predicted_slots").set(plan.planned_slots)
        if plan.capacity is not None:
            rec.gauge("plan.capacity").set(plan.capacity)
        for tactic, count in plan.tactic_counts().items():
            rec.gauge(f"plan.tactic.{tactic}").set(count)
        occ = [bp.occupancy for bp in plan.blocks if bp.nnz]
        if occ:
            rec.gauge("plan.mean_occupancy").set(float(np.mean(occ)))
        if plan.residency == "disk":
            rec.gauge("plan.io_bytes_per_iter").set(plan.io_bytes_per_iter())

    def _prepare_disk(self, spec: GimvSpec, strategy: str, theta: float | None):
        """residency='disk': never materialize the stripes — plan from the
        manifest's persisted measurements and build the schedule-driven
        executor (repro.store.residency) that streams shard slices per
        launch-schedule step with double-buffered prefetch."""
        from repro.store import DiskExecutor, make_disk_step
        from repro.store import plan_from_manifest

        if strategy == "hybrid":
            return self._prepare_disk_hybrid(spec, theta)
        if self.backend == "pallas":
            raise ValueError(
                "residency='disk' runs the streamed per-block xla path; "
                "backend='pallas' is not available out of core")
        if strategy == "vertical" and self.exchange in ("dense", "hier"):
            raise ValueError(
                "residency='disk' streams through the compact sparse or "
                f"packed exchange; exchange={self.exchange!r} is not supported")
        if self.payload_dtype is not None:
            raise ValueError("payload_dtype is not supported out of core")
        part = Partition(n=self.n, b=self.b, psi=self.psi)
        interpret = (jax.default_backend() != "tpu"
                     if self.pallas_interpret is None else self.pallas_interpret)
        capacity = None
        if strategy == "vertical":
            if self.capacity_mode == "structural":
                capacity = self.store.partial_cap
            else:
                capacity = cost_model.capacity_from_cost_model(
                    self.b, self.n, self._num_edges(),
                    stats=self.store.graph_stats(), theta=None,
                    slack=self.slack)
        scatter = (self.scatter
                   if has_semiring(spec.combine2, spec.combine_all) else "segment")
        rec = self.obs
        with rec.span("prepare.plan") as sp:
            sp.set("spec", spec.name)
            sp.set("strategy", strategy)
            plan = plan_from_manifest(
                self.store, strategy=strategy, mode="xla", theta=theta,
                capacity=capacity, scatter=scatter,
                stream="on" if strategy == "vertical" else "off",
                interpret=interpret, residency="disk")
            sp.set("predicted_slots", plan.planned_slots)
        self._record_plan_metrics(plan)
        exchange, xplan, xchg, decision = self._resolve_disk_exchange(
            spec, strategy, capacity, plan, part)
        delta_reason = None
        if self.delta_eps is not None:
            # delta needs per-row carry state across the executor's python
            # loop; the out-of-core tier keeps the full (stateless) stream.
            delta_reason = "residency='disk' keeps the full stream"
        striping = "vertical" if strategy == "vertical" else "horizontal"
        with rec.span("prepare.store"):
            dstore = self._disk_store(striping, spec, rec)
            executor = DiskExecutor(spec, part, plan, dstore, capacity=capacity,
                                    scatter=plan.scatter, interpret=interpret,
                                    obs=rec, retry=self.io_retry,
                                    exchange=exchange, xchg=xchg, xplan=xplan)
        step = make_disk_step(spec, executor)
        cfg = StepConfig(strategy=strategy, n_local=part.n_local,
                         exchange=exchange, capacity=capacity,
                         payload_dtype=None, backend="xla",
                         interpret=interpret,
                         stream="on" if strategy == "vertical" else "off",
                         plan=plan, xplan=xplan)
        real_mask_dev = self._disk_mask(part)
        meta = {
            "strategy": strategy, "theta": theta, "capacity": capacity,
            "part": part, "pm": None, "hm": None, "cfg": cfg,
            "backend": "xla", "plan": plan, "residency": "disk",
            "store": dstore, "executor": executor, "n_dense": 0,
            "exchange": exchange, "exchange_decision": decision,
            "delta_eps": None, "delta_reason": delta_reason,
        }
        return step, dstore, real_mask_dev, meta

    def _disk_store(self, striping: str, spec: GimvSpec, rec, *,
                    dense_gather_idx=None):
        """The block store serving one striping of this solve: a single
        DiskBlockStore in emulation mode (mesh=None), a per-worker
        :class:`~repro.store.SpmdDiskGroup` under a mesh — each mesh device
        gets a shard view owning its stripe range, its OWN
        ``store_budget_bytes`` residency budget, and its own prefetch
        thread (mesh size must divide b)."""
        from repro.store import DiskBlockStore, SpmdDiskGroup

        if self.mesh is None:
            return DiskBlockStore(self.store, striping, spec,
                                  budget_bytes=self.store_budget_bytes,
                                  obs=rec, faults=self._fault_injector,
                                  dense_gather_idx=dense_gather_idx)
        return SpmdDiskGroup.build(self.store, striping, spec, self.mesh,
                                   self.axis_name,
                                   budget_bytes=self.store_budget_bytes,
                                   obs=rec, faults=self._fault_injector,
                                   dense_gather_idx=dense_gather_idx)

    def _disk_mask(self, part: Partition):
        real_mask_dev = jnp.asarray(part.global_ids_grid() < self.n)
        if self.mesh is not None:
            real_mask_dev = jax.device_put(
                real_mask_dev, NamedSharding(self.mesh, P(self.axis_name)))
        return real_mask_dev

    def _prepare_disk_hybrid(self, spec: GimvSpec, theta: float | None):
        """strategy='hybrid' out of core: runs from the θ-split shards the
        ingest persisted (``ingest_edges(..., theta=...)`` writes
        sparse_vertical + dense_horizontal stripings).  The schedule is
        structural (no planner plan — ``plan_from_manifest`` has no hybrid
        disk plan, and the launch order cannot change the result: both legs
        fold order-independently), capacity covers the SPARSE region only,
        and the exchange is the compact sparse stream (the packed index
        shards describe FULL vertical stripes, not the sparse region)."""
        from repro.store import HybridDiskExecutor, make_disk_step

        if self.backend == "pallas":
            raise ValueError(
                "residency='disk' runs the streamed per-block xla path; "
                "backend='pallas' is not available out of core")
        if self.payload_dtype is not None:
            raise ValueError("payload_dtype is not supported out of core")
        if self.exchange not in ("sparse", "auto"):
            raise ValueError(
                "hybrid out-of-core streams the compact sparse exchange; "
                f"exchange={self.exchange!r} is not supported (the packed "
                "index shards describe full vertical stripes, not the "
                "sparse region)")
        stored = self.store.hybrid_theta()   # raises if no θ-split shards
        if theta is not None and float(theta) != stored:
            raise ValueError(
                f"theta={theta} does not match the store's θ-split shards "
                f"(θ={stored}) — re-ingest with that θ, or pass "
                f"theta={stored} / theta='auto'")
        theta = stored
        part = Partition(n=self.n, b=self.b, psi=self.psi)
        interpret = (jax.default_backend() != "tpu"
                     if self.pallas_interpret is None else self.pallas_interpret)
        if self.capacity_mode == "structural":
            capacity = int(self.store.hybrid["sparse_partial_cap"])
        else:
            capacity = cost_model.capacity_from_cost_model(
                self.b, self.n, self._num_edges(),
                stats=self.store.graph_stats(), theta=theta, slack=self.slack)
        # the disk tier streams the xla path, where 'auto' (and the kernel
        # gate) always lands on the segment combine — same resolution
        # plan_from_manifest applies for the basic strategies.
        scatter = (self.scatter
                   if has_semiring(spec.combine2, spec.combine_all) else "segment")
        if scatter == "auto":
            scatter = "segment"
        rec = self.obs
        region, _slot_of = self.store.dense_region()
        with rec.span("prepare.store") as sp:
            sp.set("spec", spec.name)
            sp.set("strategy", "hybrid")
            sparse_store = self._disk_store("sparse_vertical", spec, rec)
            dense_store = self._disk_store(
                "dense_horizontal", spec, rec,
                dense_gather_idx=region.gather_idx)
            executor = HybridDiskExecutor(
                spec, part, sparse_store, dense_store, region,
                capacity=capacity, scatter=scatter, interpret=interpret,
                obs=rec, retry=self.io_retry)
        step = make_disk_step(spec, executor)
        cfg = StepConfig(strategy="hybrid", n_local=part.n_local,
                         exchange="sparse", capacity=capacity,
                         payload_dtype=None, backend="xla",
                         interpret=interpret, stream="off",
                         plan=None, xplan=None)
        delta_reason = None
        if self.delta_eps is not None:
            delta_reason = "residency='disk' keeps the full stream"
        meta = {
            "strategy": "hybrid", "theta": theta, "capacity": capacity,
            "part": part, "pm": None, "hm": None, "cfg": cfg,
            "backend": "xla", "plan": None, "residency": "disk",
            "store": sparse_store, "executor": executor,
            "n_dense": int(np.asarray(region.d_count).sum()),
            "exchange": "sparse",
            "exchange_decision": "hybrid disk: compact sparse-region stream",
            "delta_eps": None, "delta_reason": delta_reason,
        }
        return step, sparse_store, self._disk_mask(part), meta

    def _resolve_disk_exchange(self, spec: GimvSpec, strategy: str,
                               capacity: int | None, plan, part):
        """Out-of-core counterpart of ``_resolve_exchange``: the per-pair
        index sets come from the store's v2 packed index shards (decoded,
        never the edge shards).  A forced 'packed' against a v1 store raises
        :class:`~repro.store.manifest.ManifestVersionError`; 'auto' degrades
        to the padded stream with the reason recorded."""
        exchange = self.exchange
        if strategy != "vertical" or capacity is None:
            if exchange in ("packed", "auto"):
                exchange = "sparse"
            return exchange, None, None, "n/a"
        if exchange not in ("packed", "auto"):
            return exchange, None, None, "forced"
        if not self.store.has_packed_index:
            if exchange == "packed":
                self.store.require_packed_index()  # raises ManifestVersionError
            return "sparse", None, None, (
                "auto: store format v%d has no packed index shards"
                % self.store.version)
        with self.obs.span("prepare.exchange") as sp:
            row_sets = self.store.packed_row_sets()
            xp, arrays = exchange_plan.build_exchange(
                row_sets, part.n_local, scatter=plan.scatter)
            sp.set("p_cap", xp.p_cap)
            sp.set("id_bytes", xp.id_bytes)
        decision = "forced"
        if exchange == "auto":
            use_packed = cost_model.prefer_packed_exchange(
                self.b, capacity, xp.payload_slots, xp.id_bytes,
                None, self._wire_itemsize(spec))
            exchange = "packed" if use_packed else "sparse"
            decision = ("auto: packed undercuts padded" if use_packed
                        else "auto: padded stream kept")
        if exchange != "packed":
            return exchange, None, None, decision
        return exchange, xp, arrays, decision

    def _resolve_stream(self, strategy: str, backend: str, capacity: int | None,
                        part: Partition) -> str:
        """Resolve the streaming knob for this prepared solve.  Only the
        planned vertical/hybrid COMPACT path has partials to stream: the
        horizontal step never materializes partials, the dense exchange
        ships them whole, and the forced backends' scan paths already
        stream — a forced 'on' degrades to 'off' there.  'auto' asks the
        cost model's memory crossover (tiny b keeps the fused launches)."""
        streamable = (backend == "planned" and capacity is not None and
                      (strategy == "hybrid" or
                       (strategy == "vertical" and
                        self.exchange in ("sparse", "hier", "packed", "auto"))))
        if not streamable:
            return "off"
        if self.stream == "auto":
            return ("on" if cost_model.prefer_streamed(self.b, part.n_local, capacity)
                    else "off")
        return self.stream

    def _resolve_backend(self, spec: GimvSpec) -> str:
        """Resolve the execution mode: 'auto' -> 'planned' (the per-block
        planner) when the spec's semiring has kernels, else 'xla'; a forced
        'pallas' likewise degrades to 'xla' without kernel support."""
        kernels_ok = has_semiring(spec.combine2, spec.combine_all)
        if self.backend == "auto":
            return "planned" if kernels_ok else "xla"
        if self.backend == "pallas" and not kernels_ok:
            return "xla"
        return self.backend

    def explain(self, spec: GimvSpec, ctx: dict | None = None, *,
                live: bool = False, live_iters: int = 3) -> str:
        """Human-readable report of the prepared ExecutionPlan: per-block
        tactic, nnz, max in-degree, padding occupancy and predicted cost,
        plus plan-level aggregates (tactic counts, flat -> bucketed padded
        slots).  Prepares (and caches) the solve as a side effect.

        ``live=True`` additionally runs a short traced probe solve
        (``live_iters`` iterations, convergence disabled) with a temporary
        recorder swapped onto the engine (and the disk executor/store when
        out of core) and appends measured-vs-predicted timings, per-iteration
        wall/exchange series and I/O overlap to the report.  The engine's own
        ``obs`` recorder is restored afterwards."""
        _step, _matrix, _v0, _ctx, _mask, meta = self.prepare(spec, ctx)
        extra = {"spec": spec.name,
                 "exchange": meta.get("exchange", self.exchange)}
        if meta["hm"] is not None:
            extra["dense_region_vertices"] = meta["n_dense"]
        if meta["plan"] is None:
            # hybrid out-of-core bypasses the planner: there is nothing
            # tactic-shaped to format, but explain() still reports the shape.
            text = ("hybrid out-of-core: structural schedule over the "
                    "θ-split shards (sparse_vertical + dense_horizontal)\n"
                    f"  theta={meta['theta']}  capacity={meta['capacity']}"
                    f"  dense_region_vertices={meta['n_dense']}")
        else:
            text = planner.format_plan(meta["plan"], extra=extra)
        xsec = self._format_exchange_section(spec, meta)
        if xsec:
            text = text + "\n" + xsec
        if not live:
            return text
        from repro.obs import Recorder
        from repro.obs.report import format_live_report

        probe = Recorder()
        targets = [self]
        if meta["residency"] == "disk":
            targets += [meta["executor"], meta["store"]]
        saved = [(t, t.obs) for t in targets]
        try:
            for t in targets:
                t.obs = probe
            # tol=0.0 never converges, so the probe runs exactly live_iters
            # iterations; overflow fallback is disabled — a probe should
            # report the configured path, not silently measure another one.
            self.run(spec, ctx, max_iters=live_iters, tol=0.0,
                     _allow_fallback=False)
        finally:
            for t, o in saved:
                t.obs = o
        return text + "\n" + format_live_report(probe, plan=meta["plan"])

    def _format_exchange_section(self, spec: GimvSpec, meta) -> str | None:
        """The explain() exchange section (per-pair index-set sizes, packed
        bit widths, predicted bytes/iter under both transports, and the
        prefer_packed_exchange decision).  When the packed arrays were not
        built (sparse/dense modes), the byte model is estimated from the
        structural partial-nnz template so the comparison still renders."""
        if meta["strategy"] == "horizontal" or meta["capacity"] is None:
            return None
        cfg = meta["cfg"]
        xp = cfg.xplan
        estimated = False
        if xp is None:
            pm, hm = meta.get("pm"), meta.get("hm")
            if meta["strategy"] == "vertical" and pm is not None:
                nnz = pm.partial_nnz
            elif hm is not None:
                nnz = hm.sparse_partial_nnz
            else:
                return None
            xp = exchange_plan.summarize_row_sizes(
                exchange_plan.row_sets_from_nnz_template(np.asarray(nnz)),
                meta["part"].n_local)
            estimated = True
        sec = exchange_plan.format_exchange(
            xp, mode=meta.get("exchange", self.exchange),
            decision=meta.get("exchange_decision", "n/a"),
            capacity=meta["capacity"], itemsize=self._wire_itemsize(spec),
            delta_eps=cfg.delta_eps, estimated=estimated)
        reason = meta.get("delta_reason")
        if self.delta_eps is not None and reason not in (None, "active"):
            sec += f"\n  delta iteration      requested but OFF: {reason}"
        return sec

    def _capacity(self, pm: PartitionedMatrix, hm: HybridMatrix | None) -> int:
        if self.capacity_mode == "structural":
            return hm.sparse_partial_cap if hm is not None else pm.partial_cap
        m = self._num_edges()
        return cost_model.capacity_from_cost_model(
            self.b, self.n, m,
            stats=pm.stats, theta=hm.theta if hm is not None else None,
            slack=self.slack,
        )

    # ------------------------------------------------------------------
    def run(
        self,
        spec: GimvSpec,
        ctx: dict | None = None,
        *,
        max_iters: int = 100,
        tol: float = 1e-6,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        _allow_fallback: bool = True,
    ) -> PMVResult:
        obs = self.obs
        with obs.span("pmv.setup"):
            step, matrix, v, ctx_b, mask, meta = self.prepare(spec, ctx)
            part: Partition = meta["part"]
            cfg: StepConfig = meta["cfg"]

            # delta-iteration carried state: the previously-shipped packed
            # payload, fresh-initialized to the combineAll identity (a
            # suppressed row then delivers the identity — a no-op — until it
            # first moves).
            xstate = None
            if cfg.delta_eps is not None:
                wire_dt = jnp.dtype(self.payload_dtype or spec.dtype)
                xstate = jnp.full((self.b, self.b, cfg.xplan.p_dev),
                                  jnp.asarray(spec.identity, wire_dt))
                if self.mesh is not None:
                    xstate = jax.device_put(
                        xstate, NamedSharding(self.mesh, P(self.axis_name)))

            start_iter = 0
            if resume and checkpoint_dir and os.path.exists(_ckpt_path(checkpoint_dir)):
                try:
                    v_np, start_iter = _ckpt_load(checkpoint_dir)
                except CheckpointCorruptError as e:
                    # _ckpt_save commits atomically (tmp + os.replace), so a
                    # corrupt state file means external truncation/disk fault
                    # — restart from v0 rather than crash the solve.
                    warnings.warn(f"ignoring corrupt checkpoint: {e}",
                                  CheckpointCorruptWarning, stacklevel=2)
                    start_iter = 0
                else:
                    v = jnp.asarray(v_np) if self.mesh is None else jax.device_put(
                        jnp.asarray(v_np), NamedSharding(self.mesh, P(self.axis_name)))

        per_iter: list[dict] = []
        converged = False
        it = start_iter
        for it in range(start_iter, max_iters):
            if self._fault_injector is not None:
                # kill events fire HERE (top of the iteration, before any
                # work) so a checkpointed run dies at a clean boundary and
                # resume=True replays from the last saved iteration bitwise.
                self._fault_injector.on_iteration(it)
            t0 = time.perf_counter()
            with obs.span("pmv.iteration") as sp:
                with obs.span("pmv.dispatch"):
                    if xstate is not None:
                        v_new, delta, stats, xstate = step(matrix, v, ctx_b, mask, xstate)
                    else:
                        v_new, delta, stats = step(matrix, v, ctx_b, mask)
                # waits for the step's execution: the span ends when the
                # device's result is back on the host
                with obs.span("pmv.sync"):
                    delta = float(delta)
                sp.set("iteration", it)
                sp.set("delta", delta)
            wall = time.perf_counter() - t0
            with obs.span("pmv.stats"):
                # store_worker_* breakdowns are per-worker LISTS; everything
                # else is a scalar.
                rec = {k: ([float(np.asarray(e)) for e in x]
                           if isinstance(x, list) else float(np.asarray(x)))
                       for k, x in stats.items()}
                rec.update(delta=delta, wall_s=wall, iteration=it)
                rec["io_elems"] = self._paper_io(meta, rec)
                per_iter.append(rec)
                if obs.enabled:
                    obs.counter("pmv.iterations").add(1)
                    obs.series("pmv.delta").append(delta)
                    obs.series("pmv.iter_wall_s").append(wall)
                    obs.series("pmv.exchanged_bytes").append(
                        rec.get("exchanged_bytes", 0.0))
                    obs.series("pmv.gathered_bytes").append(
                        rec.get("gathered_bytes", 0.0))
                    if "store_bytes_read" in rec:  # disk residency: per-iter I/O
                        obs.series("pmv.io_bytes").append(rec["store_bytes_read"])
                        obs.series("pmv.io_overlap").append(rec["store_overlap"])
            v = v_new
            if rec.get("overflow", 0.0) > 0:
                fb = self.fallback_overrides(meta["strategy"]) if _allow_fallback else None
                if fb is not None:
                    label, overrides = fb
                    obs.counter("pmv.fallbacks").add(1)
                    obs.counter(f"pmv.fallback_events.{label}").add(1)
                    result = self._fallback_engine(meta, overrides).run(
                        spec, ctx,
                        max_iters=max_iters, tol=tol,
                        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
                        resume=False, _allow_fallback=False,
                    )
                    result.totals["fallback"] = label
                    return result
                raise RuntimeError(
                    "sparse exchange overflow: capacity "
                    f"{meta['capacity']} too small — rerun with capacity='structural' "
                    "or exchange='dense'")
            if checkpoint_dir and checkpoint_every and (it + 1) % checkpoint_every == 0:
                with obs.span("pmv.checkpoint"):
                    _ckpt_save(checkpoint_dir, np.asarray(v), it + 1)
            if delta < tol:
                converged = True
                it += 1
                break
        else:
            it = max_iters

        with obs.span("pmv.result"):
            v_np = part.from_blocked(np.asarray(v))
            totals = {
                "physical_elems": sum(r.get("gathered_elems", 0.0) + r.get("exchanged_elems", 0.0) for r in per_iter),
                "logical_elems": sum(r.get("logical_elems", 0.0) for r in per_iter),
                "wall_s": sum(r["wall_s"] for r in per_iter),
                "exchanged_bytes": sum(r.get("exchanged_bytes", 0.0) for r in per_iter),
                "gathered_bytes": sum(r.get("gathered_bytes", 0.0) for r in per_iter),
            }
            if per_iter and "exchange_id_bytes" in per_iter[0]:
                # packed transport: ids crossed the wire ONCE (prepare-time
                # shipment), so the total counts them once; the padded stream
                # re-ships its int32 ids every iteration.
                id_per_iter = per_iter[0]["exchange_id_bytes"]
                totals["exchange_id_bytes"] = (
                    id_per_iter if meta.get("exchange") == "packed"
                    else sum(r.get("exchange_id_bytes", 0.0) for r in per_iter))
                totals["exchange_payload_bytes"] = sum(
                    r.get("exchange_payload_bytes", 0.0) for r in per_iter)
                totals["wire_bytes"] = (totals["exchange_id_bytes"]
                                        + totals["exchange_payload_bytes"])
            if per_iter and "delta_sent_rows" in per_iter[0]:
                totals["delta_sent_rows"] = sum(r["delta_sent_rows"] for r in per_iter)
                totals["delta_suppressed_rows"] = sum(
                    r["delta_suppressed_rows"] for r in per_iter)
            totals.update(self._io_totals(per_iter))
        return PMVResult(
            v=v_np, iterations=it, converged=converged,
            strategy=meta["strategy"], theta=meta["theta"], capacity=meta["capacity"],
            per_iter=per_iter, totals=totals,
        )


    _IO_TOTAL_KEYS = ("store_bytes_read", "store_blocks_fetched",
                      "store_blocks_skipped", "store_io_s", "store_wait_s",
                      "store_compute_s")

    @classmethod
    def _io_totals(cls, per_iter: list[dict]) -> dict:
        """Uniform disk-I/O leg of ``PMVResult.totals``: the DiskExecutor's
        per-iteration ``io_stats()`` summed over the run, and the same keys
        zeroed (overlap = 1.0, nothing to hide) for resident runs — callers
        never branch on residency to read them."""
        totals = {k: sum(r.get(k, 0.0) for r in per_iter)
                  for k in cls._IO_TOTAL_KEYS}
        io_s, wait_s = totals["store_io_s"], totals["store_wait_s"]
        totals["store_overlap"] = (max(0.0, 1.0 - wait_s / io_s)
                                   if io_s > 0.0 else 1.0)
        return totals

    def fallback_overrides(self, strategy: str) -> tuple[str, dict] | None:
        """Overflow recovery (optimistic execution, sparse_exchange.py): the
        model capacity truncated a partial, so retry once with an
        overflow-free configuration.  vertical -> dense exchange (the
        documented fallback); hybrid -> structural capacity (its compact
        exchange has no dense variant).  Public: repro.serving uses the same
        table for its requeue-on-overflow path."""
        if strategy == "vertical" and self.residency == "disk":
            # the disk executor only streams the compact exchange, so the
            # overflow-free retry is the structural capacity, not 'dense'
            if self.capacity_mode != "structural":
                return "structural_capacity", {"capacity": "structural"}
            return None
        if strategy == "vertical" and self.exchange != "dense":
            return "dense", {"exchange": "dense"}
        if strategy == "hybrid" and self.capacity_mode != "structural":
            return "structural_capacity", {"capacity": "structural"}
        return None

    def _fallback_engine(self, meta, overrides: dict) -> "PMVEngine":
        kwargs = dict(
            strategy=meta["strategy"], theta=meta["theta"], psi=self.psi,
            exchange=self.exchange, capacity=self.capacity_mode, slack=self.slack,
            payload_dtype=self.payload_dtype, delta_eps=self.delta_eps,
            backend=self.backend,
            scatter=self.scatter, stream=self.stream,
            pallas_interpret=self.pallas_interpret, base_weights=self.base_weights,
            mesh=self.mesh, axis_name=self.axis_name, obs=self.obs,
            faults=self._fault_injector, io_retry=self.io_retry,
        )
        kwargs.update(overrides)
        if self.store is not None:
            return PMVEngine(None, store=self.store, residency=self.residency,
                             store_budget_bytes=self.store_budget_bytes, **kwargs)
        # edges were already symmetrized in __init__ if requested
        return PMVEngine(self.edges, self.n, b=self.b, **kwargs)

    def _paper_io(self, meta, rec) -> float:
        """Per-iteration I/O in vector elements, the paper's metric:
        horizontal: (b+1)|v| (Lemma 3.1);
        vertical:   2|v| + 2 Σ|v^(i,j)|_nonzero (Lemma 3.2, measured);
        hybrid:     |v|P_out + b|v_d| + |v| + 2 Σ|v_s^(i,j)| (Lemma 3.3)."""
        n, b = self.n, self.b
        strategy = meta["strategy"]
        logical = rec.get("logical_elems", 0.0)
        if strategy == "horizontal":
            return (b + 1.0) * n
        if strategy == "vertical":
            return 2.0 * n + 2.0 * logical
        n_dense = meta["n_dense"]
        p_out = 1.0 - n_dense / n
        return n * p_out + b * n_dense + n + 2.0 * logical


# ---------------------------------------------------------------------------
class CheckpointCorruptError(RuntimeError):
    """The on-disk resume state is unreadable (truncated / not an npz)."""


class CheckpointCorruptWarning(UserWarning):
    """Raised-to-warning form: the solve restarted from v0."""


def _ckpt_path(d: str) -> str:
    return os.path.join(d, "pmv_state.npz")


def _ckpt_save(d: str, v: np.ndarray, it: int) -> None:
    """Atomic checkpoint commit: the full npz is written to a temp file and
    ``os.replace``d over the live one, so a crash mid-write leaves either
    the previous complete checkpoint or the new complete one — never a
    truncated file."""
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, "pmv_state.tmp.npz")
    np.savez(tmp, v=v, it=it)
    os.replace(tmp, _ckpt_path(d))  # atomic commit


def _ckpt_load(d: str) -> tuple[np.ndarray, int]:
    import zipfile

    path = _ckpt_path(d)
    try:
        with np.load(path) as z:
            return z["v"], int(z["it"])
    except (zipfile.BadZipFile, ValueError, EOFError, OSError, KeyError) as e:
        raise CheckpointCorruptError(f"{path}: {e}") from e
