"""Observability overhead + cost-model calibration (ISSUE 6): BENCH_obs.json.

Two measurements feed the JSON:

- **overhead**: the same streamed vertical PageRank solved with obs off
  (NULL_RECORDER) and obs on (enabled Recorder, per-iteration spans and
  series; the resident loop does not fence).  The disabled path must be
  free — its median wall ratio vs a plain untraced run is the headline
  number; the enabled ratio quantifies what recording costs.
- **calibration**: per-kind predicted-vs-measured residuals joining every
  launch span's wall time against the planner's cost predictions —
  ``launch.ell`` / ``launch.dense`` from the standalone block profiler,
  ``launch.disk_block`` + ``store.fetch`` (disk_io) from a disk-residency
  solve, and ``spmd_io`` / ``spmd_overlap`` from a W=4 SPMD disk solve (run
  in a subprocess so the emulated multi-device mesh exists; the same gate
  applies with per-worker trace shards enabled).  The per-kind ``ratio`` is
  the constant a self-calibrating cost model (ROADMAP item 5) would fold
  into SLOT_TIME_S / DISK_READ_BW.

Usage: PYTHONPATH=src:. python benchmarks/fig_obs_overhead.py [--smoke]
Writes BENCH_obs.json in the working directory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core import PMVEngine, pagerank
from repro.graph import erdos_renyi
from repro.obs import Recorder, bench_obs_doc, write_bench_obs
from repro.obs.profiler import profile_block_launches
from repro.store import ingest_edges

N, B = 512, 8
M_SPARSE = 3_000          # ell-tactic regime (low block density)
M_DENSE = 40_000          # dense-tactic regime (block density past the MXU crossover)
ITERS = 8
SOLVES = 5


def _median_wall(engine_kwargs, edges, n, spec, solves) -> float:
    walls = []
    eng = PMVEngine(edges, n, b=B, **engine_kwargs)
    eng.run(spec, max_iters=2)  # warm: partition + compile
    for _ in range(solves):
        t0 = time.perf_counter()
        eng.run(spec, max_iters=ITERS, tol=0.0)
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls))


SPMD_WORKERS = 4


def _spmd_series(smoke: bool) -> dict:
    """W=4 SPMD disk series from the subprocess child (the mesh's emulated
    device count must be set before jax imports, so not importable here).
    The child is a CPU emulation, run with JAX_PLATFORMS=cpu so it never
    contends with this process for an accelerator."""
    child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "spmd_obs_child.py")
    cmd = [sys.executable, child, "--workers", str(SPMD_WORKERS)]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(f"spmd child failed:\n{proc.stderr}")
    return json.loads(proc.stdout)


def main(smoke: bool = False) -> int:
    solves = 2 if smoke else SOLVES
    edges = erdos_renyi(N, M_SPARSE, seed=11)
    spec = pagerank(N)
    base = dict(strategy="vertical", backend="auto")

    # -- overhead: off must be free, on pays only for recording -------------
    wall_plain = _median_wall(base, edges, N, spec, solves)
    wall_off = _median_wall({**base, "obs": None}, edges, N, spec, solves)
    wall_on = _median_wall({**base, "obs": Recorder()}, edges, N, spec, solves)
    overhead = {
        "iters": ITERS, "solves": solves,
        "wall_plain_s": wall_plain,
        "wall_obs_off_s": wall_off,
        "wall_obs_on_s": wall_on,
        "off_ratio": wall_off / wall_plain,
        "on_ratio": wall_on / wall_plain,
    }
    print(f"overhead: off {overhead['off_ratio']:.3f}x"
          f"  on {overhead['on_ratio']:.3f}x  (vs plain, {solves} solves)")

    # -- calibration: ell + dense launches (profiler) -----------------------
    rec_ell = profile_block_launches(
        PMVEngine(edges, N, b=B, **base), spec, repeats=1 if smoke else 3)
    dense_edges = erdos_renyi(N, M_DENSE, seed=12)
    rec_dense = profile_block_launches(
        PMVEngine(dense_edges, N, b=B, **base), spec,
        repeats=1 if smoke else 3)

    # -- calibration: disk launches + fetches (out-of-core solve) -----------
    rec_disk = Recorder()
    with tempfile.TemporaryDirectory() as store_dir:
        ingest_edges(edges, N, B, store_dir)
        PMVEngine(None, store=store_dir, residency="disk",
                  strategy="vertical", obs=rec_disk).run(
            spec, max_iters=2 if smoke else ITERS, tol=0.0)

    # -- SPMD: same overhead gate with per-worker trace shards enabled ------
    spmd = _spmd_series(smoke)
    overhead["spmd"] = {k: spmd[k] for k in
                        ("workers", "wall_plain_s", "wall_obs_off_s",
                         "wall_obs_on_s", "off_ratio", "on_ratio")}
    print(f"overhead[spmd W={spmd['workers']}]:"
          f" off {spmd['off_ratio']:.3f}x  on {spmd['on_ratio']:.3f}x")

    doc = bench_obs_doc(
        {"profile_ell": rec_ell, "profile_dense": rec_dense, "disk": rec_disk},
        overhead=overhead,
        meta={"n": N, "b": B, "m_sparse": M_SPARSE, "m_dense": M_DENSE,
              "smoke": smoke},
        extra_launches=spmd["launches"],
        fleet=spmd["fleet"])
    write_bench_obs("BENCH_obs.json", doc)

    missing = ({"ell", "dense", "disk_block", "disk_io", "spmd_io",
                "spmd_overlap"} - set(doc["calibration"]))
    for kind, s in doc["calibration"].items():
        print(f"calibration[{kind}]: {s['launches']} launches"
              f"  ratio {s['ratio']:.1f}x"
              f"  median {s['ratio_median']:.1f}x")
    if missing:
        print(f"FAIL: calibration kinds missing: {sorted(missing)}")
        return 1
    if not spmd["bitwise"]:
        print("FAIL: SPMD traced solve != untraced solve")
        return 1
    # the disabled recorder must not cost more than measurement noise —
    # single-host and SPMD alike (child shards must stay free when off)
    if overhead["off_ratio"] > 1.15:
        print(f"FAIL: obs-off overhead {overhead['off_ratio']:.3f}x > 1.15x")
        return 1
    if spmd["off_ratio"] > 1.15:
        print(f"FAIL: SPMD obs-off overhead {spmd['off_ratio']:.3f}x > 1.15x")
        return 1
    print("wrote BENCH_obs.json")
    return 0


if __name__ == "__main__":
    sys.exit(main(smoke="--smoke" in sys.argv[1:]))
