#!/usr/bin/env python3
"""Bring-up smoke of PMV on a TPU: the main paths through their entry points.

    python chip_smoke.py                 # one chip: four phases
    python chip_smoke.py --chips 4       # four chips: the mesh paths only
    python chip_smoke.py --scale 22      # the full deployment (see below)

The deployment is Graphalytics' graph500-22: a Graph500 Kronecker graph
(``repro.graph.rmat``, A/B/C = 0.57/0.19/0.19, edgefactor 16) generated from
``--seed`` and symmetrized.  The default run cuts it to scale 20 and prints
the cut and its reason: at scale 22 one run does not fit 1200 s (see
DEFAULT_SCALE).  Every phase checks its answer against a plain NumPy / SciPy
reference written in this file, independent of ``repro.core``.

One chip (b = 16, all 16 workers emulated on the device):
  1. resident hybrid PageRank, backend='auto' (the planner), with θ at the
     out-degree of the DENSE_HUBS-th largest hub, so the dense region is
     non-empty and runs on the MXU kernel (see hub_theta);
  2. resident vertical WCC (min_src semiring) over the packed exchange;
  3. out-of-core vertical PageRank from an ingested block store with a
     residency budget of half the block set — bitwise the resident run;
  4. PMVServer answering 16 RWR queries (one Q=16 bucket).

Four chips (b = 4 over a 4-device 'workers' mesh):
  a. resident vertical PageRank (packed exchange) and resident hybrid
     PageRank (θ as in phase 1), each compared with the same engine without
     a mesh on device 0;
  b. SPMD out-of-core vertical PageRank at W = 4, every worker's budget below
     its share of the block set — bitwise the resident run.

Earlier lines print per-phase sizes, timings and errors; they are
informational, not benchmark measurements.  The last line is one JSON
object naming the device.  The script exits non-zero, printing no result,
when JAX finds no TPU, when the repository is not next to it, or when any
phase fails; it never falls back to the CPU or to interpret mode.

The compile cache goes to $JAX_COMPILATION_CACHE_DIR when that is set, and
to ``.jax_cache/`` next to this file otherwise.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


def log(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# Plain references (NumPy / SciPy), independent of repro.core
# ---------------------------------------------------------------------------

def ref_transition(edges: np.ndarray, n: int):
    """A[i, j] = 1 / out(j) for every edge j -> i (column-stochastic)."""
    import scipy.sparse as sp

    src, dst = edges[:, 0], edges[:, 1]
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    vals = 1.0 / np.maximum(out_deg[src], 1.0)
    return sp.csr_matrix((vals, (dst, src)), shape=(n, n))


def ref_pagerank(a, n: int, iters: int, damping: float = 0.85) -> np.ndarray:
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        x = (1.0 - damping) / n + damping * (a @ x)
    return x


def ref_rwr(a, n: int, sources, iters: int, c: float = 0.85) -> np.ndarray:
    """[n, len(sources)] random walks with restart, ``iters`` steps each."""
    restart = np.zeros((n, len(sources)))
    restart[np.asarray(sources), np.arange(len(sources))] = 1.0
    x = restart.copy()
    for _ in range(iters):
        x = (1.0 - c) * restart + c * (a @ x)
    return x


def ref_wcc(edges: np.ndarray, n: int) -> np.ndarray:
    """Component labels, each the minimum vertex id of its component."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    g = sp.csr_matrix((np.ones(len(edges), np.int8), (edges[:, 0], edges[:, 1])),
                      shape=(n, n))
    _, comp = connected_components(g, directed=True, connection="weak")
    first = np.full(comp.max() + 1, n, np.int64)
    np.minimum.at(first, comp, np.arange(n))
    return first[comp]


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| / max |want|."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-300))


# ---------------------------------------------------------------------------
# Checks shared by the phases
# ---------------------------------------------------------------------------

class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def tree_bytes(tree) -> int:
    import jax

    return int(sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree)))


def device_set_sizes(tree) -> set:
    import jax

    return {len(x.sharding.device_set) for x in jax.tree.leaves(tree)
            if hasattr(x, "sharding")}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_compiled(meta, *, planned: bool) -> None:
    """No fallback that hides the device: compiled kernels, and the planner
    when it was asked for (engine._resolve_backend would degrade silently)."""
    cfg = meta["cfg"]
    check(cfg.interpret is False, f"interpret mode on the chip: {cfg.interpret}")
    if planned:
        check(meta["backend"] == "planned",
              f"backend degraded to {meta['backend']!r}")


def check_no_fallback(result) -> None:
    check("fallback" not in result.totals,
          f"overflow fallback ran another engine: {result.totals.get('fallback')}")


def describe(name: str, meta, matrix) -> None:
    part = meta["part"]
    plan = meta.get("plan")
    tactics = plan.tactic_counts() if plan is not None else None
    log(f"[{name}] b={part.b} n_local={part.n_local} strategy={meta['strategy']}"
        f" theta={meta['theta']} backend={meta['backend']}"
        f" interpret={meta['cfg'].interpret} exchange={meta.get('exchange')}"
        f" stream={meta['cfg'].stream} scatter="
        f"{plan.scatter if plan is not None else None} tactics={tactics}"
        f" dense_region_vertices={meta['n_dense']}")
    log(f"[{name}] matrix device bytes={tree_bytes(matrix)}")


def timed_solve(name: str, eng, spec, ctx=None, **run_kw):
    """prepare, first call (compile + one iteration), then the timed solve."""
    t0 = time.perf_counter()
    _step, matrix, _v0, _ctx, _mask, meta = eng.prepare(spec, ctx)
    prep_s = time.perf_counter() - t0
    describe(name, meta, matrix)
    t0 = time.perf_counter()
    eng.run(spec, ctx, max_iters=1, tol=0.0)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = eng.run(spec, ctx, **run_kw)
    solve_s = time.perf_counter() - t0
    log(f"[{name}] prepare_s={prep_s:.3f} compile_s(first call, incl. 1 iter)="
        f"{compile_s:.3f} solve_s={solve_s:.3f} iterations={res.iterations}"
        f" converged={res.converged} peak_device_bytes={peak_bytes()}")
    check_no_fallback(res)
    return res, meta, matrix


def free() -> None:
    import jax

    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# Graph
# ---------------------------------------------------------------------------

def make_graph(scale: int, seed: int):
    from repro.graph import rmat
    from repro.graph.generators import symmetrize_edges

    t0 = time.perf_counter()
    n = 1 << scale
    # Graph500 permutes vertex labels after the Kronecker recursion; without
    # it the low ids carry the hubs and every partition's blocks are skewed.
    perm = np.random.default_rng(seed).permutation(n)
    edges = symmetrize_edges(perm[rmat(scale, 16 << scale, seed=seed)])
    log(f"[graph] graph500-{scale} seed={seed} n={n} edge_slots={len(edges)}"
        f" edge_bytes(12 B/slot)={12 * len(edges)}"
        f" build_s={time.perf_counter() - t0:.3f}")
    return edges, n


DEPLOYMENT_SCALE = 22
MIN_SCALE = 20
# On one TPU v5e (host clock), graph500-22 spent about 800 s in phases 1-3
# (graph build, host partitioning, ingest, references) and its serving phase,
# then 20 RWR iterations, was still running 1100 s later; graph500-20 ran all
# four phases in 395 s.  A run has to finish within 1200 s.
DEFAULT_SCALE = 20
SCALE_CUT_REASON = ("one run must finish within 1200 s; at scale 22 phases 1-3"
                    " alone took ~800 s on one v5e")

# θ = 'auto' puts θ* above every Graph500 out-degree (64,702 at scale 20
# against a largest out-degree far below it), which leaves the dense region
# empty; the hybrid phases take θ at the out-degree of the 64th-largest hub.
DENSE_HUBS = 64
PR_ITERS = 20
DISK_ITERS = 5
RWR_ITERS = 5
PR_RTOL = 1e-4


def hub_theta(edges: np.ndarray, n: int) -> float:
    """θ at the out-degree of the DENSE_HUBS-th largest vertex: the dense
    region holds those hubs (and any tied with the last)."""
    out_deg = np.bincount(edges[:, 0], minlength=n)
    return float(np.partition(out_deg, n - DENSE_HUBS)[n - DENSE_HUBS])


def check_dense_region(name: str, meta, matrix) -> None:
    """The hybrid's dense region is non-empty and materialized for the MXU
    kernel (engine: backend='planned' runs it through dense_gimv)."""
    check(meta["n_dense"] > 0, "the dense region is empty")
    check("dense_matrix" in matrix, "the dense region is not run by the dense kernel")
    log(f"[{name}] dense region: vertices={meta['n_dense']}"
        f" d_cap={matrix['dense_region'].d_cap}"
        f" dense_matrix shape={tuple(matrix['dense_matrix'].shape)}")


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def phase_hybrid_pagerank(edges, n, b, a_ref):
    from repro.core import PMVEngine, cost_model, pagerank

    spec = pagerank(n)
    eng = PMVEngine(edges, n, b=b, strategy="hybrid", theta=hub_theta(edges, n),
                    backend="auto")
    res, meta, matrix = timed_solve("1 hybrid-pagerank", eng, spec,
                                    max_iters=PR_ITERS, tol=0.0)
    check_compiled(meta, planned=True)
    check_dense_region("1 hybrid-pagerank", meta, matrix)
    counts = meta["plan"].tactic_counts()
    check(counts["ell"] > 0, f"no ell-tactic block in the plan: {counts}")
    if counts["dense"] == 0:
        # the planner's per-block rule (cost_model.dense_block_cost): a block
        # goes dense only when n_local^2 / MXU_SLOT_ADVANTAGE undercuts its
        # ELL slots, at a density no Graph500 block reaches.
        worst = max(bp.cost for bp in meta["plan"].blocks)
        log(f"[1 hybrid-pagerank] no dense-tactic block: dense cost"
            f" {cost_model.dense_block_cost(meta['part'].n_local):.0f} slots"
            f" > costliest ell block {worst:.0f} slots")
    err = rel_err(res.v, ref_pagerank(a_ref, n, PR_ITERS))
    log(f"[1 hybrid-pagerank] max_rel_err_vs_scipy={err:.3e} (limit {PR_RTOL})")
    check(err <= PR_RTOL, f"hybrid PageRank off the reference: {err}")


def phase_wcc(edges, n, b):
    from repro.core import PMVEngine, connected_components

    spec = connected_components()
    eng = PMVEngine(edges, n, b=b, strategy="vertical", exchange="packed",
                    backend="auto")
    res, meta, _ = timed_solve("2 vertical-wcc", eng, spec, max_iters=100, tol=0.5)
    check_compiled(meta, planned=True)
    check(meta["exchange"] == "packed", f"exchange resolved to {meta['exchange']}")
    check(res.converged, "WCC did not converge in 100 iterations")
    want = ref_wcc(edges, n)
    wrong = int(np.count_nonzero(np.asarray(res.v) != want))
    log(f"[2 vertical-wcc] components={len(np.unique(want))}"
        f" mismatched_labels={wrong} (limit 0)")
    check(wrong == 0, f"WCC labels differ from scipy on {wrong} vertices")


def phase_disk_pagerank(edges, n, b, a_ref):
    from repro.core import PMVEngine, pagerank
    from repro.store import ingest_edges

    spec = pagerank(n)
    resident = PMVEngine(edges, n, b=b, strategy="vertical")
    r_res, meta_r, _ = timed_solve("3 resident-vertical", resident, spec,
                                   max_iters=DISK_ITERS, tol=0.0)
    check_compiled(meta_r, planned=False)
    del resident
    free()
    with tempfile.TemporaryDirectory(prefix="pmv_smoke_store_") as d:
        t0 = time.perf_counter()
        man = ingest_edges(edges, n, b, os.path.join(d, "store"))
        block_set = man.total_shard_bytes("vertical")
        log(f"[3 disk-vertical] ingest_s={time.perf_counter() - t0:.3f}"
            f" block_set_bytes={block_set} budget_bytes={block_set // 2}")
        eng = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                                   store_budget_bytes=block_set // 2)
        r_disk, meta_d, _ = timed_solve("3 disk-vertical", eng, spec,
                                        max_iters=DISK_ITERS, tol=0.0)
        check_compiled(meta_d, planned=False)
        tot = r_disk.totals
        log(f"[3 disk-vertical] store_bytes_read={tot['store_bytes_read']:.0f}"
            f" store_io_s={tot['store_io_s']:.3f} store_wait_s={tot['store_wait_s']:.3f}"
            f" overlap={tot['store_overlap']:.3f}")
        meta_d["executor"].close()
    bitwise = bool(np.array_equal(r_disk.v, r_res.v))
    err = rel_err(r_disk.v, ref_pagerank(a_ref, n, DISK_ITERS))
    log(f"[3 disk-vertical] bitwise_equal_resident={bitwise}"
        f" max_rel_err_vs_scipy={err:.3e} (limit {PR_RTOL})")
    check(bitwise, "out-of-core result differs from the resident run")
    check(err <= PR_RTOL, f"disk PageRank off the reference: {err}")


def phase_serving(edges, n, b, a_ref, seed):
    from repro.serving import PMVServer, Query

    rng = np.random.default_rng(seed + 1)
    sources = rng.choice(n, size=16, replace=False)
    server = PMVServer(edges, n, b=b, strategy="vertical", backend="auto",
                       buckets=(16,))
    queries = [Query(spec_kind="rwr", source=int(s), tol=0.0, max_iters=RWR_ITERS)
               for s in sources]
    t0 = time.perf_counter()
    results = server.serve(queries)
    serve_s = time.perf_counter() - t0
    stats = server.stats()
    # the family's prepared solve (one family: rwr, c=0.85)
    (state,) = server._families.values()
    meta = state.meta
    describe("4 serving-rwr", meta, state.matrix)
    check_compiled(meta, planned=True)
    log(f"[4 serving-rwr] queries={len(results)} batches={stats['batches']}"
        f" serve_s(incl. prepare+compile)={serve_s:.3f}"
        f" peak_device_bytes={peak_bytes()}")
    check(not stats["fallback_events"], f"fallback events: {stats['fallback_events']}")
    check(all(r.reason == "completed" and r.iterations == RWR_ITERS for r in results),
          "a query did not run its iterations to completion")
    picks = [0, len(results) - 1]
    want = ref_rwr(a_ref, n, [int(sources[k]) for k in picks], RWR_ITERS)
    errs = [rel_err(results[k].vector, want[:, i]) for i, k in enumerate(picks)]
    log(f"[4 serving-rwr] checked queries {picks} max_rel_err_vs_scipy="
        f"{max(errs):.3e} (limit {PR_RTOL})")
    check(max(errs) <= PR_RTOL, f"RWR answers off the reference: {errs}")
    server.close()


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def phase_mesh(edges, n, b, a_ref, mesh):
    from repro.core import PMVEngine, pagerank

    spec = pagerank(n)
    for name, kw in (("a vertical-packed", dict(strategy="vertical", exchange="packed",
                                                backend="auto")),
                     ("a hybrid", dict(strategy="hybrid", theta=hub_theta(edges, n),
                                       backend="auto"))):
        single = PMVEngine(edges, n, b=b, **kw)
        r_one, _, _ = timed_solve(f"{name} 1-device", single, spec,
                                  max_iters=PR_ITERS, tol=0.0)
        del single
        free()
        eng = PMVEngine(edges, n, b=b, mesh=mesh, **kw)
        r_mesh, meta, matrix = timed_solve(f"{name} mesh", eng, spec,
                                           max_iters=PR_ITERS, tol=0.0)
        check_compiled(meta, planned=True)
        if kw["strategy"] == "hybrid":
            check_dense_region(f"{name} mesh", meta, matrix)
        spans = device_set_sizes(matrix)
        log(f"[{name} mesh] matrix leaves span {sorted(spans)} devices")
        check(spans == {mesh.size}, f"matrix not spread over the mesh: {spans}")
        diff = rel_err(r_mesh.v, np.asarray(r_one.v, np.float64))
        err = rel_err(r_mesh.v, ref_pagerank(a_ref, n, PR_ITERS))
        log(f"[{name}] mesh_vs_1device_max_rel_diff={diff:.3e}"
            f" bitwise={bool(np.array_equal(r_mesh.v, r_one.v))}"
            f" max_rel_err_vs_scipy={err:.3e} (limit {PR_RTOL})")
        check(diff <= PR_RTOL and err <= PR_RTOL, f"{name}: mesh run off")
        del eng
        free()


def phase_spmd_disk(edges, n, b, mesh):
    from repro.core import PMVEngine, cost_model, pagerank
    from repro.store import ingest_edges

    spec = pagerank(n)
    resident = PMVEngine(edges, n, b=b, strategy="vertical")
    r_res, _, _ = timed_solve("b resident-vertical", resident, spec,
                              max_iters=DISK_ITERS, tol=0.0)
    del resident
    free()
    with tempfile.TemporaryDirectory(prefix="pmv_smoke_store_") as d:
        man = ingest_edges(edges, n, b, os.path.join(d, "store"))
        # one destination block's slice of a worker's b/W stripes, with the
        # recomputed weights: the budget holds the double buffer, half of
        # the worker's b slices.
        slice_bytes = cost_model.stripe_slice_bytes(b // mesh.size, man.e_cap,
                                                    has_w=True)
        share, budget = b * slice_bytes, 2 * slice_bytes
        log(f"[b spmd-disk] workers={mesh.size} per_worker_share_bytes={share}"
            f" per_worker_budget_bytes={budget}")
        check(budget < share, "budget does not undercut the worker's share")
        eng = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                                   mesh=mesh, store_budget_bytes=budget)
        r_disk, meta, _ = timed_solve("b spmd-disk", eng, spec,
                                      max_iters=DISK_ITERS, tol=0.0)
        check_compiled(meta, planned=False)
        spans = device_set_sizes(eng.prepare(spec)[4])
        log(f"[b spmd-disk] mask spans {sorted(spans)} devices")
        check(spans == {mesh.size}, f"SPMD state not spread over the mesh: {spans}")
        meta["executor"].close()
    bitwise = bool(np.array_equal(r_disk.v, r_res.v))
    log(f"[b spmd-disk] bitwise_equal_resident={bitwise}")
    check(bitwise, "SPMD out-of-core result differs from the resident run")


# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} TPU devices, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices


def use_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=DEFAULT_SCALE,
                    help=f"Graph500 scale (log2 vertices); never below {MIN_SCALE}")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.scale < MIN_SCALE:
        ap.error(f"--scale below {MIN_SCALE} is not a real deployment")

    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro  # noqa: F401  (fails here when the repository is absent)

    use_compile_cache()
    t_start = time.perf_counter()
    log(f"[device] platform={devices[0].platform} kind={devices[0].device_kind}"
        f" count={len(devices)} cache_dir="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or os.path.join(ROOT, '.jax_cache')}")
    if args.scale < DEPLOYMENT_SCALE:
        reason = SCALE_CUT_REASON if args.scale == DEFAULT_SCALE else "set by --scale"
        log(f"[cut] graph500-{args.scale} in place of graph500-{DEPLOYMENT_SCALE}:"
            f" {reason}")
    edges, n = make_graph(args.scale, args.seed)
    a_ref = ref_transition(edges, n)
    if args.chips == 1:
        b = 16
        phase_hybrid_pagerank(edges, n, b, a_ref)
        free()
        phase_wcc(edges, n, b)
        free()
        phase_disk_pagerank(edges, n, b, a_ref)
        free()
        phase_serving(edges, n, b, a_ref, args.seed)
    else:
        from repro.core.mesh import worker_mesh

        b = 4
        mesh = worker_mesh(4, devices=devices[:4])
        phase_mesh(edges, n, b, a_ref, mesh)
        free()
        phase_spmd_disk(edges, n, b, mesh)
    log(f"[done] all phases passed in {time.perf_counter() - t_start:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
