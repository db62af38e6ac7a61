"""PMVServer: pre-partition once, answer many concurrent GIM-V queries.

The paper amortizes pre-partitioning across the *iterations* of one solve
(§3.1); serving amortizes it across *queries*.  The resident matrix stripes
stay on device while query vectors come and go as columns of a blocked
[b, n_local, Q] batch — every placement (placement.py) carries the trailing
query axis through its collectives, so one iteration of the batched step
advances all in-flight queries at the cost of one matrix traversal.

Continuous batching: each query column tracks its own convergence delta; a
converged column is retired (result extracted, latency recorded) and a
waiting query of the same family is admitted into the freed column mid-loop
without disturbing the others — the GIM-V semirings are columnwise
independent, so an admitted column's trajectory is bitwise the trajectory it
would have had in a fresh batch.  Batches are padded to fixed Q buckets
(batcher.py) so jit specializes once per bucket size.

Degradation under pressure (ISSUE 7): per-query ``deadline_s`` budgets
(anchored at submit — an expired column retires with its partial iterate),
``max_queue`` admission control (overloaded submits shed immediately instead
of growing every deadline behind them), and batch-level failure containment
(an I/O / integrity error that survives the retry layer fails THAT batch's
queries with a typed diagnosis; the server keeps serving).  Every retirement
carries a reason — completed | deadline_exceeded | shed | failed — tallied
in ``stats()['retirement_reasons']``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithms
from repro.core.engine import PMVEngine, StepConfig, _squeeze0, placement_call
from repro.core.gimv import GimvSpec
from repro.core.mesh import as_auto_mesh
from repro.faults import FetchDeadlineError, as_injector
from repro.obs import as_recorder, as_telemetry
from repro.serving.batcher import (
    DEFAULT_BUCKETS,
    RETIREMENT_REASONS,
    Query,
    QueryBatcher,
    QueryResult,
)

__all__ = ["PMVServer", "QueryFamily", "FAMILIES", "make_batched_step", "per_query_delta"]


# ---------------------------------------------------------------------------
# Query families: algorithm kind -> spec + per-query column construction.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryFamily:
    """How to turn queries of one kind into columns of a batched solve.

    delta_kind: 'abs' (sum |dv|, the PR/RWR metric) or 'count' (changed
      entries — SSSP/CC, whose +-inf distances make abs-deltas NaN).
    empty_column: neutral fill for padded / retired-and-unreplaced columns;
      frozen by the active mask but must stay finite under combine2.
    """

    kind: str
    delta_kind: str
    make_spec: Callable[[int, Query], GimvSpec]
    init_column: Callable[[int, Query], np.ndarray]
    ctx_columns: Callable[[int, Query], dict[str, np.ndarray]]
    empty_column: Callable[[int], np.ndarray]
    symmetrize: bool = False


def _onehot(n: int, i: int) -> np.ndarray:
    x = np.zeros(n, np.float32)
    x[i] = 1.0
    return x


FAMILIES: dict[str, QueryFamily] = {
    "pagerank": QueryFamily(
        kind="pagerank",
        delta_kind="abs",
        make_spec=lambda n, q: algorithms.pagerank(n, damping=q.c),
        init_column=lambda n, q: np.full(n, 1.0 / n, np.float32),
        ctx_columns=lambda n, q: {},
        empty_column=lambda n: np.zeros(n, np.float32),
    ),
    "rwr": QueryFamily(
        kind="rwr",
        delta_kind="abs",
        make_spec=lambda n, q: algorithms.random_walk_with_restart(n, source=q.source, c=q.c),
        init_column=lambda n, q: _onehot(n, q.source),
        ctx_columns=lambda n, q: algorithms.rwr_context(n, q.source),
        empty_column=lambda n: np.zeros(n, np.float32),
    ),
    "sssp": QueryFamily(
        kind="sssp",
        delta_kind="count",
        make_spec=lambda n, q: algorithms.sssp(source=q.source),
        init_column=lambda n, q: np.where(np.arange(n) == q.source, np.float32(0.0), np.float32(np.inf)),
        ctx_columns=lambda n, q: {},
        empty_column=lambda n: np.full(n, np.inf, np.float32),
    ),
    "cc": QueryFamily(
        kind="cc",
        delta_kind="count",
        make_spec=lambda n, q: algorithms.connected_components(),
        init_column=lambda n, q: np.arange(n, dtype=np.int32),
        ctx_columns=lambda n, q: {},
        empty_column=lambda n: np.arange(n, dtype=np.int32),
        symmetrize=True,
    ),
}


# ---------------------------------------------------------------------------
# Batched step: placement with a trailing query axis + per-query convergence.
# ---------------------------------------------------------------------------

def per_query_delta(v, v_new, *, delta_kind: str):
    """Per-column convergence contribution: [.., n_local, Q] -> [Q]."""
    axes = tuple(range(v_new.ndim - 1))
    if delta_kind == "count":
        return jnp.sum((v_new != v).astype(jnp.float32), axis=axes)
    return jnp.sum(jnp.abs(v_new - v), axis=axes)


def make_batched_step(spec: GimvSpec, cfg: StepConfig, mesh=None, axis_name: str = "workers",
                      *, delta_kind: str = "abs"):
    """Build step(matrix, v, ctx, mask, active) -> (v_new, deltas [Q], stats).

    v/ctx carry a trailing query axis ([b, n_local, Q] in emulation,
    [n_local, Q] per worker in SPMD).  ``active`` [Q] freezes retired /
    padded columns: their v entries pass through unchanged, so a column can
    sit retired while the rest of the batch keeps iterating.
    """

    def _advance(matrix, v, ctx, mask, active, axis):
        v_new, _r, stats = placement_call(spec, cfg, matrix, v, ctx, mask, axis)
        v_new = jnp.where(active, v_new, v)  # broadcast over trailing Q axis
        return v_new, per_query_delta(v, v_new, delta_kind=delta_kind), stats

    if mesh is None:
        def step(matrix, v, ctx, mask, active):
            return _advance(matrix, v, ctx, mask, active, None)
        return jax.jit(step, donate_argnums=(1,))

    from jax.sharding import PartitionSpec as P

    def body(matrix, v, ctx, mask, active):
        matrix_, v_, ctx_, mask_ = (_squeeze0(t) for t in (matrix, v, ctx, mask))
        v_new, deltas, stats = _advance(matrix_, v_, ctx_, mask_, active, axis_name)
        deltas = jax.lax.psum(deltas, axis_name)
        return v_new[None], deltas, stats

    sharded, repl = P(axis_name), P()
    step = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded, repl),
        out_specs=(sharded, repl, repl),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(1,))


def _make_disk_batched_step(executor, *, delta_kind: str):
    """Batched step over an out-of-core store (residency='disk'): the
    DiskExecutor walks the launch schedule exactly as in the scalar path —
    the trailing query axis rides through single_block_compact's batched
    compaction — and only the active-column freeze + per-query deltas are
    applied here."""

    @partial(jax.jit, donate_argnums=())
    def _freeze(v, v_cand, active):
        v_new = jnp.where(active, v_cand, v)
        return v_new, per_query_delta(v, v_new, delta_kind=delta_kind)

    def step(matrix, v, ctx, mask, active):
        del matrix
        v_cand, _delta, stats = executor.iteration(v, ctx, mask)
        v_new, deltas = _freeze(v, v_cand, active)
        return v_new, deltas, stats

    return step


@partial(jax.jit, donate_argnums=(0, 1))
def _admit_columns(v, ctx, slot_idx, v_cols, ctx_cols):
    """Admit one iteration's queries in a single donated scatter.

    v: [b, n_local, Q] (donated — updated in place on device), slot_idx: [k]
    freed column indices, v_cols: [b, n_local, k] init columns.  Batching the
    admissions and donating the buffers replaces the per-query eager
    ``.at[].set`` (which copied the full multi-GB state once per admitted
    query) with one fused scatter per iteration.
    """
    v = v.at[:, :, slot_idx].set(v_cols)
    ctx = {k: ctx[k].at[:, :, slot_idx].set(ctx_cols[k]) for k in ctx}
    return v, ctx


# ---------------------------------------------------------------------------
# The server.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _FamilyState:
    family: QueryFamily
    spec: GimvSpec
    engine: PMVEngine
    step: Callable
    matrix: object
    mask: object
    part: object
    meta: dict


class PMVServer:
    """Multi-query GIM-V serving over one resident pre-partitioned matrix.

    submit() enqueues queries; drain() packs them into Q-bucket batches per
    family, iterates the batched step with per-query convergence tracking,
    and continuously admits waiting queries into retired columns.  Everything
    expensive — partitioning, device-resident stripes, jit — is cached per
    family across batches (and across drain calls).
    """

    def __init__(
        self,
        edges: np.ndarray | None = None,
        n: int | None = None,
        *,
        b: int | None = None,
        strategy: str = "selective",
        theta: float | str = "auto",
        psi: str | None = None,  # None: 'cyclic', or the store's ψ
        exchange: str = "sparse",
        capacity: str = "structural",
        slack: float = 1.5,
        payload_dtype: str | None = None,
        backend: str = "xla",
        scatter: str = "auto",
        stream: str = "auto",
        pallas_interpret: bool | None = None,
        base_weights: np.ndarray | None = None,
        buckets: tuple[int, ...] = DEFAULT_BUCKETS,
        max_iters: int = 200,
        mesh=None,
        axis_name: str = "workers",
        store=None,
        residency: str = "device",
        store_budget_bytes: int | None = None,
        obs=None,
        faults=None,
        io_retry=None,
        max_queue: int | None = None,
        telemetry=None,
    ):
        self.store = None
        self.residency = residency
        self.store_budget_bytes = store_budget_bytes
        if store is not None:
            # manifest-backed serving: the resident matrix comes from an
            # ingested block store (path or Manifest); n/b/psi are its.
            from repro.store import open_store

            self.store = open_store(store)
            if edges is not None:
                raise ValueError("pass either edges or store=, not both")
            if n is not None and int(n) != self.store.n:
                raise ValueError(f"n={n} does not match the store's n={self.store.n}")
            if b is not None and int(b) != self.store.b:
                raise ValueError(f"b={b} does not match the store's b={self.store.b}")
            n, b = self.store.n, self.store.b
            self.edges = None
        else:
            if edges is None or n is None or b is None:
                raise ValueError("PMVServer needs (edges, n, b=) or store=")
            self.edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        self.n = int(n)
        self.b = int(b)
        self.max_iters = int(max_iters)
        self.mesh = as_auto_mesh(mesh)
        self.axis_name = axis_name
        # obs is shared with every family engine (and through it the disk
        # executor/store), so one recorder traces the whole serving run.
        self.obs = as_recorder(obs)
        self._engine_kwargs = dict(
            strategy=strategy, theta=theta, psi=psi, exchange=exchange,
            capacity=capacity, slack=slack, payload_dtype=payload_dtype,
            backend=backend, scatter=scatter, stream=stream,
            pallas_interpret=pallas_interpret,
            base_weights=base_weights, mesh=self.mesh, axis_name=axis_name,
            # normalized ONCE so every family engine shares one injector —
            # a FaultPlan's events fire once server-wide, not once per family
            obs=self.obs, faults=as_injector(faults, self.obs),
            io_retry=io_retry,
        )
        # admission control: queries submitted while >= max_queue are waiting
        # are shed immediately (reason='shed') instead of growing the backlog
        # without bound.  None = accept everything (the default).
        self.max_queue = max_queue
        # live telemetry: rolling-window latency/throughput + SLO burn rates
        # over the retirement ledger, optionally exported over HTTP
        # (repro.obs.live).  Host-side bookkeeping only — cannot change a
        # served result.  True -> defaults; TelemetryConfig / LiveTelemetry
        # accepted; None/False -> off.
        self.telemetry = as_telemetry(
            telemetry, registry=self.obs.metrics if self.obs.enabled else None)
        if self.telemetry is not None and self.telemetry.config.serve:
            self.telemetry.start_server()
        self._batcher = QueryBatcher(buckets)
        self._families: dict[tuple, _FamilyState] = {}
        self._family_overrides: dict[tuple, dict] = {}  # overflow fallbacks
        self._results: dict[int, QueryResult] = {}
        self._next_qid = 0
        self._fallback_events: list[str] = []  # fallback labels, batch order
        self._occupancy_sum = 0.0              # sum over batches of |queries|/Q
        self._retirement_reasons = {r: 0 for r in RETIREMENT_REASONS}
        self._stats = {
            "batches": 0, "queries": 0, "admitted_mid_batch": 0,
            "overflow_fallbacks": 0, "retired": 0, "requeued": 0,
            "shed": 0, "failed_batches": 0,
            "queue_wait_s": 0.0,
            "iterations": 0.0, "gathered_elems": 0.0, "exchanged_elems": 0.0,
            "logical_elems": 0.0, "wall_s": 0.0,
        }

    # ------------------------------------------------------------------
    def submit(self, query: Query) -> int:
        """Enqueue a query; returns its qid (key into drain()'s results).

        Load shedding: when ``max_queue`` is set and that many queries are
        already waiting, the query is refused up front — drain() returns a
        ``reason='shed'`` result for its qid (vector None) instead of letting
        the backlog (and every deadline behind it) grow without bound.
        """
        if not 0 <= query.source < self.n:
            raise ValueError(
                f"query source {query.source} out of range for |V|={self.n}")
        if query.qid is not None:  # resubmission: don't alias the old entry
            query = dataclasses.replace(query, qid=None, t_submit=None)
        qid = self._next_qid
        self._next_qid += 1
        query.qid = qid
        query.t_submit = time.perf_counter()
        self._stats["queries"] += 1
        if self.max_queue is not None and len(self._batcher) >= self.max_queue:
            self._retire_unserved(query, "shed")
            self._stats["shed"] += 1
            self.obs.counter("serve.shed").add(1)
            return qid
        self._batcher.add(query)
        if self.telemetry is not None:
            self.telemetry.record_queue_depth(len(self._batcher))
        return qid

    def _retire_unserved(self, query: Query, reason: str,
                         error: str | None = None) -> None:
        """Record a result for a query whose column never (or no longer)
        iterates: shed at admission or lost to a failed batch."""
        latency = time.perf_counter() - query.t_submit
        self._results[query.qid] = QueryResult(
            qid=query.qid, query=query, vector=None, iterations=0,
            converged=False, latency_s=latency,
            reason=reason, error=error,
        )
        self._retirement_reasons[reason] += 1
        if self.telemetry is not None:
            self.telemetry.record_retirement(
                reason, latency, had_deadline=query.deadline_s is not None)

    def drain(self) -> dict[int, QueryResult]:
        """Serve every queued query to convergence; returns {qid: result}."""
        while True:
            nxt = self._batcher.next_batch()
            if nxt is None:
                break
            key, batch = nxt
            self._run_batch(key, batch)
        out, self._results = self._results, {}
        return out

    def serve(self, queries: list[Query]) -> list[QueryResult]:
        """submit() + drain(), results in submission order."""
        qids = [self.submit(q) for q in queries]
        results = self.drain()
        return [results[qid] for qid in qids]

    def stats(self) -> dict:
        """Serving counters: batches/queries/iterations plus the retirement
        ledger — ``retired`` answered columns, ``requeued`` queries sent back
        through the batcher by an overflow fallback, ``fallback_events``
        (the fallback labels, batch order), total ``queue_wait_s`` and mean
        ``batch_occupancy`` (real queries / bucket capacity)."""
        out = dict(self._stats)
        out["fallback_events"] = list(self._fallback_events)
        out["retirement_reasons"] = dict(self._retirement_reasons)
        out["batch_occupancy"] = (
            self._occupancy_sum / out["batches"] if out["batches"] else 0.0)
        if self.telemetry is not None:
            out["slo"] = self.telemetry.slo.snapshot()
        return out

    def close(self) -> None:
        """Release resources held beyond the serve loop (today: the
        telemetry HTTP exporter's daemon thread, if one was started)."""
        if self.telemetry is not None:
            self.telemetry.close()

    # ------------------------------------------------------------------
    def _family_state(self, key: tuple, sample: Query) -> _FamilyState:
        if key not in self._families:
            family = FAMILIES[sample.spec_kind]
            spec = family.make_spec(self.n, sample)
            kwargs = dict(self._engine_kwargs)
            kwargs.update(self._family_overrides.get(key, {}))
            if self.store is not None:
                if family.symmetrize and not self.store.symmetrized:
                    raise ValueError(
                        f"query family {family.kind!r} needs a symmetrized "
                        "graph but the store was ingested without symmetrize "
                        "— re-ingest with ingest_edges(symmetrize=True)")
                engine = PMVEngine(
                    None, store=self.store, residency=self.residency,
                    store_budget_bytes=self.store_budget_bytes,
                    symmetrize=family.symmetrize, **kwargs)
            else:
                engine = PMVEngine(self.edges, self.n, b=self.b,
                                   symmetrize=family.symmetrize, **kwargs)
            _, matrix, _v0, _ctx, mask, meta = engine.prepare(spec)
            if meta.get("residency") == "disk":
                step = _make_disk_batched_step(meta["executor"],
                                               delta_kind=family.delta_kind)
            else:
                step = make_batched_step(spec, meta["cfg"], self.mesh,
                                         self.axis_name,
                                         delta_kind=family.delta_kind)
            self._families[key] = _FamilyState(
                family=family, spec=spec, engine=engine, step=step,
                matrix=matrix, mask=mask, part=meta["part"], meta=meta,
            )
        return self._families[key]

    def _column(self, st: _FamilyState, query: Query | None):
        """(v_col [b, n_local], ctx cols) for a query (None -> neutral pad)."""
        fam, part = st.family, st.part
        if query is None:
            v_col = part.to_blocked(fam.empty_column(self.n))
            ctx_cols = {k: np.zeros((self.b, part.n_local), x.dtype) for k, x in
                        fam.ctx_columns(self.n, Query(spec_kind=fam.kind)).items()}
        else:
            v_col = part.to_blocked(fam.init_column(self.n, query))
            ctx_cols = {k: part.to_blocked(x) for k, x in fam.ctx_columns(self.n, query).items()}
        return v_col, ctx_cols

    def _run_batch(self, key: tuple, batch: list[Query]) -> None:
        from repro.store.manifest import ShardCorruptError

        obs = self.obs
        with obs.span("serve.batch") as batch_span:
            batch_span.set("family", str(key))
            try:
                self._run_batch_inner(key, batch, batch_span)
            except (ShardCorruptError, OSError, FetchDeadlineError) as e:
                # The I/O / integrity layer exhausted its retries: this batch
                # is lost, but the SERVER is not — every unanswered query in
                # it retires with reason='failed' and the typed diagnosis, and
                # later batches (other families, re-ingested stores) proceed.
                self._stats["failed_batches"] += 1
                obs.counter("serve.failed_batches").add(1)
                batch_span.set("failed", type(e).__name__)
                self._families.pop(key, None)  # state may be half-built
                for query in batch:
                    if query.qid not in self._results:
                        self._retire_unserved(query, "failed", error=str(e))

    def _run_batch_inner(self, key: tuple, batch: list[Query], batch_span) -> None:
        obs = self.obs
        st = self._family_state(key, batch[0])
        part = st.part
        n_q = self._batcher.bucket_for(len(batch))
        self._stats["batches"] += 1
        self._occupancy_sum += len(batch) / n_q
        obs.gauge("serve.batch_occupancy").set(len(batch) / n_q)
        batch_span.set("n_q", n_q)
        batch_span.set("queries", len(batch))

        slots: list[Query | None] = [None] * n_q
        v_np = np.zeros((self.b, part.n_local, n_q), st.spec.dtype)
        ctx_np: dict[str, np.ndarray] | None = None
        for q_i in range(n_q):
            query = batch[q_i] if q_i < len(batch) else None
            slots[q_i] = query
            v_col, ctx_cols = self._column(st, query)
            if ctx_np is None:
                ctx_np = {k: np.zeros((self.b, part.n_local, n_q), x.dtype)
                          for k, x in ctx_cols.items()}
            v_np[:, :, q_i] = v_col
            for k, x in ctx_cols.items():
                ctx_np[k][:, :, q_i] = x

        v = jnp.asarray(v_np)
        ctx = {k: jnp.asarray(x) for k, x in (ctx_np or {}).items()}
        active = np.array([s is not None for s in slots])
        iters = np.zeros(n_q, np.int64)
        tols = np.array([s.tol if s else 0.0 for s in slots])
        caps = np.array([(s.max_iters or self.max_iters) if s else 0 for s in slots])
        # absolute per-query deadlines (inf = none), anchored at SUBMIT time:
        # queue wait counts against the budget, as a caller's SLO would.
        dls = np.array([(s.t_submit + s.deadline_s)
                        if s is not None and s.deadline_s is not None
                        else np.inf for s in slots])
        # queue wait ends when a query's column starts iterating: now for the
        # initial slots, the admission instant for mid-batch admissions.
        t_start = time.perf_counter()
        starts = np.full(n_q, t_start)

        while active.any():
            t0 = time.perf_counter()
            with obs.span("serve.iteration") as sp:
                v_new, deltas, stats = st.step(st.matrix, v, ctx, st.mask, jnp.asarray(active))
                v_new = obs.fence(v_new)
                deltas = np.asarray(deltas)
                sp.set("active", int(active.sum()))
            iter_wall = time.perf_counter() - t0
            self._stats["wall_s"] += iter_wall
            self._stats["iterations"] += 1
            if self.telemetry is not None:
                self.telemetry.record_iteration(iter_wall,
                                                active=int(active.sum()))
                self.telemetry.record_queue_depth(len(self._batcher))
            for k in ("gathered_elems", "exchanged_elems", "logical_elems"):
                self._stats[k] += float(np.asarray(stats.get(k, 0.0)))
            if float(np.asarray(stats.get("overflow", 0.0))) > 0:
                # A truncated exchange would silently corrupt EVERY in-flight
                # column (the shared index set unions rows across queries), so
                # the truncated iteration is discarded.  When an overflow-free
                # configuration exists (the engine's fallback table: vertical
                # -> dense exchange, hybrid -> structural capacity), the
                # family is rebuilt with it and the batch's in-flight queries
                # are requeued — they restart, but keep their qids so callers
                # see answers, not errors.  The default capacity='structural'
                # cannot overflow.
                fb = st.engine.fallback_overrides(st.meta["strategy"])
                if fb is None:
                    lost = sorted(q.qid for q in slots if q is not None)
                    raise RuntimeError(
                        "sparse exchange overflow in batched serving: capacity "
                        f"{st.meta['capacity']} too small for the query batch — "
                        "construct the server with capacity='structural' or "
                        f"exchange='dense'; unanswered qids in this batch: {lost}")
                label, overrides = fb
                self._stats["overflow_fallbacks"] += 1
                self._fallback_events.append(label)
                obs.counter("serve.fallbacks").add(1)
                batch_span.set("fallback", label)
                self._family_overrides[key] = {**self._family_overrides.get(key, {}),
                                               **overrides}
                del self._families[key]  # rebuilt with the fallback on requeue
                for query in slots:
                    if query is not None:
                        self._batcher.add(query)  # keeps qid -> result mapping
                        self._stats["requeued"] += 1
                return
            iters[active] += 1

            admissions: list[tuple[int, np.ndarray, dict]] = []
            now = time.perf_counter()
            for q_i in np.nonzero(active)[0]:
                done = deltas[q_i] < tols[q_i]
                expired = not done and now > dls[q_i]
                if not done and not expired and iters[q_i] < caps[q_i]:
                    continue
                # retire the converged / capped / deadline-expired column.
                # An expired query still gets its PARTIAL iterate back —
                # the caller asked for the best answer by the deadline.
                query = slots[q_i]
                reason = "deadline_exceeded" if expired else "completed"
                vec = part.from_blocked(np.asarray(v_new[:, :, q_i]))
                latency = time.perf_counter() - query.t_submit
                self._results[query.qid] = QueryResult(
                    qid=query.qid, query=query, vector=vec,
                    iterations=int(iters[q_i]), converged=bool(done),
                    latency_s=latency, reason=reason,
                )
                self._retirement_reasons[reason] += 1
                if expired:
                    obs.counter("serve.deadline_exceeded").add(1)
                self._stats["retired"] += 1
                wait = max(0.0, starts[q_i] - query.t_submit)
                self._stats["queue_wait_s"] += wait
                if self.telemetry is not None:
                    self.telemetry.record_retirement(
                        reason, latency, queue_wait_s=wait,
                        had_deadline=query.deadline_s is not None)
                if obs.enabled:
                    obs.counter("serve.retired").add(1)
                    obs.histogram("serve.query_latency_s").observe(latency)
                    obs.histogram("serve.queue_wait_s").observe(wait)
                    obs.histogram("serve.query_iterations").observe(int(iters[q_i]))
                # admit a waiting query of the same family into the freed slot
                waiting = self._batcher.pop_waiting(key)
                if waiting is not None:
                    self._stats["admitted_mid_batch"] += 1
                    batch.append(waiting)  # a later batch failure must see it
                    slots[q_i] = waiting
                    v_col, ctx_cols = self._column(st, waiting)
                    admissions.append((int(q_i), v_col, ctx_cols))
                    iters[q_i] = 0
                    tols[q_i] = waiting.tol
                    caps[q_i] = waiting.max_iters or self.max_iters
                    dls[q_i] = (waiting.t_submit + waiting.deadline_s
                                if waiting.deadline_s is not None else np.inf)
                    starts[q_i] = time.perf_counter()
                else:
                    slots[q_i] = None
                    active[q_i] = False
            if admissions:
                # one jitted, buffer-donated scatter admits the whole
                # iteration's queries (vs an eager full-state copy per query)
                slot_idx = np.array([a[0] for a in admissions], np.int32)
                v_cols = np.stack([a[1] for a in admissions], axis=-1)
                ctx_cols = {k: np.stack([a[2][k] for a in admissions], axis=-1)
                            for k in ctx}
                v_new, ctx = _admit_columns(
                    v_new, ctx, jnp.asarray(slot_idx), jnp.asarray(v_cols),
                    {k: jnp.asarray(x) for k, x in ctx_cols.items()})
            v = v_new
