"""Figure 1 analog: running time vs graph size, PMV vs a PEGASUS-like
baseline — plus the paper's actual scalability story: an OUT-OF-CORE series
(graphs whose block set exceeds a simulated device-memory budget) through
``repro.store``'s disk residency, reporting bytes-read-per-iteration and the
prefetch-overlap ratio into ``BENCH_store.json``.

PEGASUS (and every iterative MapReduce GIM-V) re-shuffles the whole matrix
every iteration; PMV shuffles it once at pre-partitioning and moves only
vectors afterwards.  The baseline here re-runs the partition+stripe build
(the shuffle analog) on every iteration; PMV amortizes it.  We report
per-iteration wall time and the modeled shuffled-element counts
(PMV: O(|v|); baseline: O(|M|+|v|), paper §3.1)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import emit
from repro.core import PMVEngine, cost_model, pagerank
from repro.core.partition import partition_graph
from repro.graph import rmat

SIZES = [(9, 8_000), (10, 16_000), (11, 32_000), (12, 64_000)]
ITERS = 8
B = 8

# Out-of-core series: sizes run against a residency budget of half the
# vertical block set — every point's "graph" is larger than its "device".
STORE_SIZES = [(10, 16_000), (11, 32_000), (12, 64_000)]
STORE_JSON = "BENCH_store.json"

# SPMD out-of-core series: the same graphs spread over a W-worker mesh,
# each worker holding a shard view of the store under a PER-WORKER budget
# smaller than the block set.  Runs in a subprocess so the emulated host
# devices can be configured before jax imports.
SPMD_WORKERS = [2, 8]
SPMD_OVERLAP_FLOOR = 0.4  # gate: the pipeline must hide ≥40% of disk time


def run():
    for log2n, m_edges in SIZES:
        n = 1 << log2n
        edges = rmat(log2n, m_edges, seed=7)
        m = len(edges)
        spec = pagerank(n)

        # --- PMV: partition once, iterate ---------------------------------
        eng = PMVEngine(edges, n, b=B, strategy="hybrid", theta="auto")
        t0 = time.perf_counter()
        res = eng.run(spec, max_iters=ITERS, tol=0.0)
        pmv_total = time.perf_counter() - t0
        pmv_per_iter = float(np.median([r["wall_s"] for r in res.per_iter[1:]]))

        # --- PEGASUS-like: re-shuffle M every iteration --------------------
        t0 = time.perf_counter()
        for _ in range(ITERS):
            partition_graph(edges, n, B, spec)  # the per-iteration M shuffle
            # (the multiply itself is the same engine step; shuffle dominates)
        baseline_shuffle = (time.perf_counter() - t0) / ITERS
        baseline_per_iter = baseline_shuffle + pmv_per_iter

        speedup = baseline_per_iter / pmv_per_iter
        io = res.per_iter[-1]["io_elems"]
        emit(f"fig1/pmv/n={n}/m={m}", pmv_per_iter * 1e6,
             f"shuffled_elems={io:.0f}")
        emit(f"fig1/pegasus_like/n={n}/m={m}", baseline_per_iter * 1e6,
             f"shuffled_elems={m + n};speedup={speedup:.1f}x;io_ratio={(m + n) / io:.1f}x")

    run_store()


def run_store(out_json: str = STORE_JSON) -> dict:
    """Out-of-core series: ingest each graph into a block store, cap the
    residency budget below the block-set bytes (the paper's 'graph larger
    than memory' regime), solve PageRank with residency='disk', and record
    bytes-read-per-iteration + prefetch overlap vs the resident engine."""
    from repro.store import ingest_edges

    results = []
    for log2n, m_edges in STORE_SIZES:
        n = 1 << log2n
        edges = rmat(log2n, m_edges, seed=7)
        spec = pagerank(n)
        with tempfile.TemporaryDirectory() as tmp:
            root = os.path.join(tmp, "store")
            t0 = time.perf_counter()
            man = ingest_edges(edges, n, B, root, chunk_edges=1 << 14)
            ingest_s = time.perf_counter() - t0
            total_bytes = man.total_shard_bytes("vertical")
            slice_bytes = cost_model.stripe_slice_bytes(B, man.e_cap, has_w=True)
            budget = max(total_bytes // 2, 3 * slice_bytes)

            eng_disk = PMVEngine(None, store=root, residency="disk",
                                 strategy="vertical",
                                 store_budget_bytes=budget)
            res_disk = eng_disk.run(spec, max_iters=ITERS, tol=0.0)
            eng_dev = PMVEngine(edges, n, b=B, strategy="vertical")
            res_dev = eng_dev.run(spec, max_iters=ITERS, tol=0.0)
            assert np.array_equal(res_disk.v, res_dev.v), "disk != device"

            tail = res_disk.per_iter[1:]
            rec = {
                "n": n, "m": len(edges), "b": B,
                "budget_bytes": int(budget),
                "block_set_bytes": int(total_bytes),
                "exceeds_budget": bool(total_bytes > budget),
                "ingest_s": ingest_s,
                "bytes_read_per_iter": float(np.median(
                    [r["store_bytes_read"] for r in tail])),
                "prefetch_overlap": float(np.median(
                    [r["store_overlap"] for r in tail])),
                "disk_iter_us": float(np.median(
                    [r["wall_s"] for r in tail])) * 1e6,
                "device_iter_us": float(np.median(
                    [r["wall_s"] for r in res_dev.per_iter[1:]])) * 1e6,
                "bitwise_equal": True,
            }
            results.append(rec)
            emit(f"fig1/store_disk/n={n}/m={len(edges)}", rec["disk_iter_us"],
                 f"bytes_per_iter={rec['bytes_read_per_iter']:.0f};"
                 f"overlap={rec['prefetch_overlap']:.2f};"
                 f"budget_frac={budget / total_bytes:.2f}")
    doc = {"series": results, "spmd_series": run_store_spmd(), "iters": ITERS}
    with open(out_json, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


# One SPMD measurement process per graph: ``--xla_force_host_platform_
# device_count`` must be set before jax imports, so the mesh runs in a
# child interpreter that reports its records back as JSON on stdout.  The
# child is a CPU emulation of W hosts (the forced device count means nothing
# on an accelerator), so it runs with JAX_PLATFORMS=cpu and never contends
# with this process for a chip.
_SPMD_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import sys
import tempfile
import time

import numpy as np
from repro.core import PMVEngine, cost_model, pagerank
from repro.core.mesh import worker_mesh
from repro.graph import rmat
from repro.store import ingest_edges

p = json.loads(sys.argv[1])
log2n, m_edges, iters, b = p["log2n"], p["m_edges"], p["iters"], p["b"]
n = 1 << log2n
edges = rmat(log2n, m_edges, seed=7)
spec = pagerank(n)
ref = PMVEngine(edges, n, b=b, strategy="vertical").run(
    spec, max_iters=iters, tol=0.0)

runs = []
with tempfile.TemporaryDirectory() as tmp:
    root = os.path.join(tmp, "store")
    man = ingest_edges(edges, n, b, root, chunk_edges=1 << 14)
    total_bytes = man.total_shard_bytes("vertical")
    slice_bytes = cost_model.stripe_slice_bytes(b, man.e_cap, has_w=True)
    for W in p["workers"]:
        # Per-worker budget: half of THIS worker's shard-view share, so the
        # union of budgets stays below the block set and each worker must
        # stream (paper's graph-exceeds-memory regime, now per host).
        budget = max(total_bytes // (2 * W), 3 * slice_bytes)
        assert budget < total_bytes, (budget, total_bytes)
        mesh = worker_mesh(W)
        eng = PMVEngine(None, store=root, residency="disk",
                        strategy="vertical", mesh=mesh,
                        store_budget_bytes=budget)
        t0 = time.perf_counter()
        res = eng.run(spec, max_iters=iters, tol=0.0)
        wall_s = time.perf_counter() - t0
        assert np.array_equal(res.v, ref.v), ("spmd-disk != resident", W)
        tail = res.per_iter[1:]
        med = lambda k: float(np.median([r[k] for r in tail]))
        wmed = lambda k: [float(x) for x in np.median(
            np.array([r[k] for r in tail], dtype=float), axis=0)]
        w_bytes, w_io = wmed("store_worker_bytes_read"), wmed("store_worker_io_s")
        w_wait, w_ov = wmed("store_worker_wait_s"), wmed("store_worker_overlap")
        # Wire split: the vector exchange is all-to-all symmetric, so each
        # worker moves an equal 1/W share of the iteration's wire bytes.
        wire_bytes_w = med("exchanged_bytes") / W
        wire_s_w = cost_model.ici_seconds(wire_bytes_w, bytes_per_elem=1)
        compute_s = max(med("wall_s") - med("store_wait_s"), 0.0)
        runs.append({
            "workers": W,
            "budget_bytes": int(budget),
            "block_set_bytes": int(total_bytes),
            "exceeds_budget": bool(total_bytes > budget),
            "bitwise_equal": True,
            "iter_us": med("wall_s") * 1e6,
            "total_wall_s": wall_s,
            "bytes_read_per_iter": med("store_bytes_read"),
            "prefetch_overlap": med("store_overlap"),
            "predicted_overlap": cost_model.predicted_overlap(
                cost_model.per_host_io_seconds(med("store_bytes_read"), W),
                wire_s_w, compute_s),
            "per_worker": [
                {"worker": k, "bytes_read": w_bytes[k], "io_s": w_io[k],
                 "wait_s": w_wait[k], "overlap": w_ov[k],
                 "wire_bytes": wire_bytes_w, "wire_s": wire_s_w}
                for k in range(W)],
        })
print("SPMD_JSON " + json.dumps(
    {"n": n, "m": len(edges), "b": b, "runs": runs}))
'''


def run_store_spmd() -> list:
    """SPMD out-of-core series: each graph solved on a W-worker mesh with
    per-worker budgets below the block set, bitwise-gated against the
    resident engine, reporting the measured prefetch overlap and the
    per-worker wire/I-O split (plus the cost model's predicted overlap).
    Each graph runs in a JAX_PLATFORMS=cpu child: the W workers are emulated
    CPU devices, and this process has already used JAX for run_store()."""
    series = []
    for log2n, m_edges in STORE_SIZES:
        params = {"log2n": log2n, "m_edges": m_edges, "iters": ITERS,
                  "b": B, "workers": SPMD_WORKERS}
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   x for x in ("src", os.environ.get("PYTHONPATH", "")) if x)}
        proc = subprocess.run(
            [sys.executable, "-c", _SPMD_SCRIPT, json.dumps(params)],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"SPMD series subprocess failed\nstdout:\n{proc.stdout}\n"
                f"stderr:\n{proc.stderr}")
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith("SPMD_JSON "))
        doc = json.loads(line[len("SPMD_JSON "):])
        for rec in doc["runs"]:
            assert rec["prefetch_overlap"] >= SPMD_OVERLAP_FLOOR, (
                f"prefetch overlap {rec['prefetch_overlap']:.2f} below the "
                f"{SPMD_OVERLAP_FLOOR} floor (n={doc['n']}, W={rec['workers']})")
            emit(f"fig1/store_spmd/n={doc['n']}/m={doc['m']}/w={rec['workers']}",
                 rec["iter_us"],
                 f"bytes_per_iter={rec['bytes_read_per_iter']:.0f};"
                 f"overlap={rec['prefetch_overlap']:.2f};"
                 f"predicted={rec['predicted_overlap']:.2f};"
                 f"budget_frac={rec['budget_bytes'] / rec['block_set_bytes']:.2f}")
        series.append(doc)
    return series


if __name__ == "__main__":
    if "--store-only" in sys.argv:
        run_store()
    else:
        run()
