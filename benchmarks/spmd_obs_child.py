"""Subprocess worker for the SPMD observability benchmarks.

``--xla_force_host_platform_device_count`` must be set before jax imports,
so the SPMD series of fig_obs_overhead / obs_smoke runs here, in a child
process, and reports one JSON document on stdout:

    walls      plain / obs-off / obs-on median solve walls (W workers)
    bitwise    obs-on solve == obs-off solve (the zero-overhead contract)
    fleet      fleet_report(...).to_dict() of the traced run
    launches   FleetReport.calibration_launches() (spmd_io / spmd_overlap)
    trace      the merged per-worker-lane Chrome trace (validated here)

Usage: python benchmarks/spmd_obs_child.py [--workers W] [--iters I]
                                           [--solves S] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--solves", type=int, default=3)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.workers}")

    import numpy as np

    from repro.core import PMVEngine, pagerank
    from repro.core.mesh import worker_mesh
    from repro.graph import erdos_renyi
    from repro.obs import (
        check_span_nesting,
        fleet_report,
        merge_traces,
        validate_chrome_trace,
    )
    from repro.store import ingest_edges

    n, b = 512, 8
    iters = 3 if args.smoke else args.iters
    solves = args.solves     # median-of-3 even in smoke: the 1.15x gate
                             # needs more than one sample against noise
    edges = erdos_renyi(n, 3_000, seed=11)
    spec = pagerank(n)
    mesh = worker_mesh(args.workers)

    def median_wall(obs):
        eng = PMVEngine(None, store=store_dir, residency="disk",
                        strategy="vertical", mesh=mesh, obs=obs)
        eng.run(spec, max_iters=2)          # warm: partition + compile
        walls = []
        for _ in range(solves):
            t0 = time.perf_counter()
            last = eng.run(spec, max_iters=iters, tol=0.0)
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)), last, eng

    with tempfile.TemporaryDirectory() as store_dir:
        ingest_edges(edges, n, b, store_dir)
        wall_plain, r_plain, _ = median_wall(None)
        wall_off, r_off, _ = median_wall(False)
        wall_on, r_on, eng_on = median_wall(True)

        doc = merge_traces(eng_on.obs)
        n_events = validate_chrome_trace(doc)
        check_span_nesting(doc)
        lanes = sorted((ev.get("args") or {}).get("name", "")
                       for ev in doc["traceEvents"]
                       if ev.get("ph") == "M" and ev["name"] == "process_name")
        rep = fleet_report(r_on)
        out = {
            "workers": args.workers,
            "iters": iters, "solves": solves,
            "wall_plain_s": wall_plain,
            "wall_obs_off_s": wall_off,
            "wall_obs_on_s": wall_on,
            "off_ratio": wall_off / wall_plain,
            "on_ratio": wall_on / wall_plain,
            "bitwise": bool(np.array_equal(r_off.v, r_on.v)
                            and np.array_equal(r_plain.v, r_on.v)),
            "trace_events": n_events,
            "lanes": lanes,
            "fleet": rep.to_dict(),
            "launches": rep.calibration_launches(),
            "trace": doc,
        }
    json.dump(out, sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
