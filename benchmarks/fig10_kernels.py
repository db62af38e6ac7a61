"""Figure 10 (beyond-paper): kernelized hot path — xla vs pallas latency.

Three comparisons, emitted as CSV lines (benchmarks.common) AND as
``BENCH_kernels.json`` (the repo's perf-trajectory artifact, uploaded by CI):

- step/<strategy>/<semiring>/q<Q>: one full per-iteration hybrid step through
  ``placement_call`` with backend='xla' vs backend='pallas', for all four
  kernel semirings and Q in {1, 16, 64} (the serving bucket sweep);
- dense_region/<semiring>: the hybrid dense-region sub-multiplication alone —
  gathered_gimv's gather+segment lowering vs the dense_gimv MXU/VPU kernel on
  the materialized [n_local, b*d_cap] matrix;
- compaction/topk_vs_scan: the sparse-exchange compaction alone — the legacy
  O(n log k) lax.top_k lowering vs the O(n) cumsum-prefix scatter that
  replaced it (sparse_exchange.compact_partials method='scan');
- ell_padding/rmat: padded ELL slots of the flat one-d_cap-per-stripe layout
  vs the planner's row-bucketed slices on a skewed (RMAT power-law) graph —
  the memory/compute win ISSUE 3's per-block ExecutionPlan buys at pack time
  (reported as slot counts + occupancy, gated on reduction > 1).

On CPU hosts the Pallas kernels run in interpret mode (what this container
measures); on TPU they lower to Mosaic.  ``--smoke`` shrinks every size for
the CI gate, which only checks the artifact exists and the microbenchmarks
report a speedup.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_iters
from repro.core import PMVEngine, connected_components, pagerank, sssp
from repro.core.engine import placement_call
from repro.core.gimv import GimvSpec
from repro.core.sparse_exchange import compact_partials
from repro.graph import rmat

RESULTS: list[dict] = []


def _record(name: str, xla_us: float, pallas_us: float, extra: str = "") -> None:
    speedup = xla_us / max(pallas_us, 1e-9)
    RESULTS.append({"name": name, "xla_us": round(xla_us, 1),
                    "pallas_us": round(pallas_us, 1),
                    "speedup": round(speedup, 3)})
    emit(name, pallas_us, f"xla_us={xla_us:.1f} speedup={speedup:.2f}x {extra}".strip())


def _max_plus_spec(n: int) -> GimvSpec:
    """Widest-accumulation semiring (add, max) — Table 2's missing fourth
    kernel semiring; assign keeps the running max (monotone relaxation)."""
    return GimvSpec(
        name="maxplus", combine2="add", combine_all="max", dtype=np.float32,
        assign=lambda v, r, ctx: jnp.maximum(v, r),
        init=lambda ids, ctx: np.zeros(ids.shape, np.float32),
    )


SEMIRING_SPECS = {
    "plus_times": lambda n: pagerank(n),
    "min_plus": lambda n: sssp(0),
    "min_src": lambda n: connected_components(),
    "max_plus": _max_plus_spec,
}


def bench_steps(scale: int, m_edges: int, b: int, qs: tuple[int, ...],
                reps: int) -> None:
    n = 1 << scale
    edges = rmat(scale, m_edges, seed=23)
    rng = np.random.default_rng(0)
    for semiring, mk in SEMIRING_SPECS.items():
        spec = mk(n)
        engines = {
            be: PMVEngine(edges, n, b=b, strategy="hybrid", theta=8.0,
                          symmetrize=(semiring == "min_src"), backend=be)
            for be in ("xla", "pallas")
        }
        prepped = {be: eng.prepare(spec) for be, eng in engines.items()}
        for q in qs:
            times = {}
            for be, (step, matrix, _v0, _ctx, mask, meta) in prepped.items():
                part = meta["part"]
                shape = (b, part.n_local) if q == 1 else (b, part.n_local, q)
                if np.dtype(spec.dtype) == np.int32:
                    v = jnp.asarray(rng.integers(0, n, shape).astype(np.int32))
                else:
                    v = jnp.asarray(rng.random(shape).astype(np.float32))
                cfg = meta["cfg"]

                @jax.jit
                def one_step(v_, _cfg=cfg, _m=matrix, _mask=mask, _spec=spec):
                    v_new, _r, _s = placement_call(_spec, _cfg, _m, v_, {}, _mask, None)
                    return v_new

                times[be] = time_iters(
                    lambda: jax.block_until_ready(one_step(v)), n_iters=reps)
            _record(f"fig10/step/hybrid/{semiring}/q{q}",
                    times["xla"], times["pallas"])


def bench_dense_region(n_local: int, b: int, d_cap: int, reps: int) -> None:
    """The dense-region sub-multiplication alone, fully dense block."""
    from repro.core.blocks import BlockEdges, materialize_dense_matrix
    from repro.core.placement import gathered_gimv
    from repro.kernels.block_gimv import dense_gimv, semiring_of

    rng = np.random.default_rng(1)
    interpret = jax.default_backend() != "tpu"
    for semiring in ("plus_times", "min_plus"):
        spec = SEMIRING_SPECS[semiring](n_local * b)
        # every (row, dense-slot) pair has an edge: E = n_local * d_cap per block
        e_cap = n_local * d_cap
        seg = np.tile(np.repeat(np.arange(n_local, dtype=np.int32), d_cap), (b, 1))
        gat = np.tile(np.tile(np.arange(d_cap, dtype=np.int32), n_local), (b, 1))
        w = rng.random((b, e_cap)).astype(np.float32)
        stripe = BlockEdges(seg_local=seg, gat_local=gat, w=w,
                            count=np.full(b, e_cap, np.int32))
        dm = materialize_dense_matrix(stripe, n_local, d_cap, semiring)
        v_d = rng.random((b, d_cap)).astype(np.float32)

        stripe_j = jax.tree.map(jnp.asarray, stripe)
        v_all = jnp.asarray(v_d)
        dm_j, v_flat = jnp.asarray(dm), jnp.asarray(v_d.reshape(-1))

        xla_fn = jax.jit(lambda va: gathered_gimv(spec, stripe_j, va, n_local))
        sr = semiring_of(spec.combine2, spec.combine_all)
        pallas_fn = jax.jit(lambda vf: dense_gimv(dm_j, vf, semiring=sr,
                                                  interpret=interpret))
        np.testing.assert_allclose(np.asarray(xla_fn(v_all)),
                                   np.asarray(pallas_fn(v_flat)),
                                   rtol=1e-3, atol=1e-3)
        xla_us = time_iters(lambda: jax.block_until_ready(xla_fn(v_all)), n_iters=reps)
        pallas_us = time_iters(lambda: jax.block_until_ready(pallas_fn(v_flat)), n_iters=reps)
        _record(f"fig10/dense_region/{semiring}", xla_us, pallas_us,
                f"n_local={n_local} K={b * d_cap}")


def bench_compaction(n_local: int, rows: int, capacity: int, reps: int) -> None:
    spec = pagerank(n_local)
    rng = np.random.default_rng(2)
    x = np.where(rng.random((rows, n_local)) < 0.05,
                 rng.random((rows, n_local)), 0.0).astype(np.float32)
    xj = jnp.asarray(x)
    fns = {
        m: jax.jit(lambda p, _m=m: compact_partials(spec, p, capacity, None, method=_m)[:2])
        for m in ("topk", "scan")
    }
    for a, b_ in zip(fns["topk"](xj), fns["scan"](xj)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    topk_us = time_iters(lambda: jax.block_until_ready(fns["topk"](xj)), n_iters=reps)
    scan_us = time_iters(lambda: jax.block_until_ready(fns["scan"](xj)), n_iters=reps)
    _record("fig10/compaction/topk_vs_scan", topk_us, scan_us,
            f"n_local={n_local} rows={rows} cap={capacity}")


def bench_ell_padding(scale: int, m_edges: int, b: int) -> None:
    """Row-bucketed ELL slices vs the flat d_cap layout on a power-law graph.

    Packs the SAME vertical stripes both ways (all blocks 'ell', the plan's
    bucket boundaries) and counts padded slots actually allocated — the
    quantity the per-iteration ELL kernels stream and VMEM holds.
    """
    from repro.core import blocks as blocks_lib, pagerank, planner
    from repro.core.partition import partition_graph

    n = 1 << scale
    edges = rmat(scale, m_edges, seed=7)
    spec = pagerank(n)
    pm, _ = partition_graph(edges, n, b, spec)
    n_local = pm.part.n_local
    plan = planner.plan_execution(pm, None, strategy="vertical", mode="planned",
                                  capacity=pm.partial_cap)
    flat = blocks_lib.stack_ells(
        [blocks_lib.stripe_to_ell(s, n_local) for s in pm.vertical])
    bucketed = blocks_lib.stack_planned(
        [blocks_lib.pack_planned_stripe(
            s, ("ell",) * b, n_local, layout="vertical",
            boundaries=plan.boundaries, semiring="plus_times",
            split=plan.split)
         for s in pm.vertical], "plus_times")
    flat_slots = int(np.asarray(flat.cols).size)
    bucketed_slots = sum(int(np.asarray(bk.cols).size) for bk in bucketed.buckets)
    nnz = int(pm.block_nnz.sum())
    reduction = flat_slots / max(bucketed_slots, 1)
    RESULTS.append({
        "name": "fig10/ell_padding/rmat",
        "flat_slots": flat_slots,
        "bucketed_slots": bucketed_slots,
        "nnz": nnz,
        "flat_occupancy": round(nnz / max(flat_slots, 1), 4),
        "bucketed_occupancy": round(nnz / max(bucketed_slots, 1), 4),
        "slot_reduction": round(reduction, 3),
        "buckets": list(plan.boundaries),
    })
    emit("fig10/ell_padding/rmat", float(bucketed_slots),
         f"flat_slots={flat_slots} reduction={reduction:.2f}x "
         f"occ {nnz / max(flat_slots, 1):.3f}->{nnz / max(bucketed_slots, 1):.3f}")


def run(smoke: bool = False, out: str = "BENCH_kernels.json") -> dict:
    RESULTS.clear()
    if smoke:
        bench_steps(scale=9, m_edges=3000, b=4, qs=(1, 16), reps=2)
        bench_dense_region(n_local=256, b=4, d_cap=64, reps=2)
        bench_compaction(n_local=1 << 15, rows=8, capacity=1024, reps=2)
        bench_ell_padding(scale=11, m_edges=12_000, b=4)
    else:
        bench_steps(scale=12, m_edges=60_000, b=4, qs=(1, 16, 64), reps=3)
        bench_dense_region(n_local=512, b=4, d_cap=128, reps=3)
        bench_compaction(n_local=1 << 17, rows=16, capacity=4096, reps=3)
        bench_ell_padding(scale=14, m_edges=200_000, b=4)
    payload = {
        "bench": "fig10_kernels",
        "smoke": smoke,
        "jax_backend": jax.default_backend(),
        "results": RESULTS,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {os.path.abspath(out)}")
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny sizes for CI")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    payload = run(smoke=args.smoke, out=args.out)
    micro = [r for r in payload["results"]
             if r["name"].startswith(("fig10/dense_region", "fig10/compaction"))]
    slow = [r for r in micro if r["speedup"] < 1.0]
    if slow:
        raise SystemExit(f"microbenchmark regression (pallas/scan slower): {slow}")
    padding = [r for r in payload["results"] if r["name"] == "fig10/ell_padding/rmat"]
    if not padding or padding[0]["slot_reduction"] <= 1.0:
        raise SystemExit(
            f"row-bucketed ELL did not reduce padded slots: {padding}")


if __name__ == "__main__":
    main()
