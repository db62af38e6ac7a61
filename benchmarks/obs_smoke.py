"""CI smoke for the observability layer (ISSUE 6).

One command runs a traced resident solve and a traced disk-residency solve
of the same PageRank, gates on the zero-overhead contract (the traced
results must be BITWISE the untraced ones), validates the exported Chrome
trace against the schema + span-nesting invariants, and writes the
artifacts the CI job uploads:

    OBS_smoke/trace.json         resident + disk spans (load in Perfetto)
    OBS_smoke/fleet_trace.json   merged SPMD trace, one lane per worker
    OBS_smoke/metrics.jsonl      metrics dump (one JSON object per metric)
    OBS_smoke/BENCH_obs.json     predicted-vs-measured calibration residuals
                                 (incl. the spmd_io/spmd_overlap kinds and
                                 the fleet straggler report)
    OBS_smoke/openmetrics.txt    one live scrape of a telemetry-enabled
                                 PMVServer's /metrics endpoint
    OBS_smoke/parity.json        bitwise parity + span inventory report

The SPMD leg runs benchmarks/spmd_obs_child.py as a child process with
JAX_PLATFORMS=cpu: it emulates four hosts on forced CPU devices, and must
never contend with this process for an accelerator.

Exits non-zero on parity failure, schema violation, nesting violation,
missing calibration kinds (ell / dense / disk_block / disk_io / spmd_io /
spmd_overlap), a malformed merged SPMD trace, or a bad scrape.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np

from repro.core import PMVEngine, pagerank
from repro.graph import rmat
from repro.obs import (
    Recorder,
    bench_obs_doc,
    check_span_nesting,
    to_chrome_trace,
    validate_chrome_trace,
    write_bench_obs,
)
from repro.obs.profiler import profile_block_launches
from repro.store import ingest_edges

LOG2N = 9
M_EDGES = 4_000
M_DENSE = 24_000
B = 8
ITERS = 5


def main(out_root: str = "OBS_smoke") -> int:
    os.makedirs(out_root, exist_ok=True)
    n = 1 << LOG2N
    edges = rmat(LOG2N, M_EDGES, seed=7)
    spec = pagerank(n)
    failures = []

    # -- resident: untraced vs traced must be bitwise identical -------------
    r_plain = PMVEngine(edges, n, b=B, strategy="vertical",
                        backend="auto").run(spec, max_iters=ITERS, tol=0.0)
    rec = Recorder()
    r_traced = PMVEngine(edges, n, b=B, strategy="vertical", backend="auto",
                         obs=rec).run(spec, max_iters=ITERS, tol=0.0)
    resident_bitwise = bool(np.array_equal(r_plain.v, r_traced.v))
    if not resident_bitwise:
        failures.append("resident traced result != untraced result")

    # -- disk: same gate, same recorder (one trace covers both) -------------
    store_dir = os.path.join(out_root, "store")
    ingest_edges(edges, n, B, store_dir)
    d_plain = PMVEngine(None, store=store_dir, residency="disk",
                        strategy="vertical").run(spec, max_iters=ITERS, tol=0.0)
    d_traced = PMVEngine(None, store=store_dir, residency="disk",
                         strategy="vertical", obs=rec).run(
        spec, max_iters=ITERS, tol=0.0)
    disk_bitwise = bool(np.array_equal(d_plain.v, d_traced.v))
    if not disk_bitwise:
        failures.append("disk traced result != untraced result")
    # the disk executor is bitwise the resident XLA vertical step (the
    # planned backend's bucketed folds reorder float sums, so the resident
    # runs above are not the right oracle for this gate)
    r_xla = PMVEngine(edges, n, b=B, strategy="vertical").run(
        spec, max_iters=ITERS, tol=0.0)
    if not np.array_equal(d_plain.v, r_xla.v):
        failures.append("disk result != resident xla result")

    # -- per-block kernel launches for the ell + dense residuals ------------
    profile_block_launches(PMVEngine(edges, n, b=B, strategy="vertical",
                                     backend="auto"), spec, obs=rec)
    profile_block_launches(PMVEngine(rmat(LOG2N, M_DENSE, seed=8), n, b=B,
                                     strategy="vertical", backend="auto"),
                           spec, obs=rec)

    # -- exports: schema + nesting gates ------------------------------------
    doc = to_chrome_trace(rec)
    try:
        n_events = validate_chrome_trace(doc)
        check_span_nesting(doc)
    except Exception as e:  # noqa: BLE001 - report, don't crash the smoke
        failures.append(f"trace validation: {e}")
        n_events = 0
    with open(os.path.join(out_root, "trace.json"), "w") as f:
        json.dump(doc, f)
    rec.write_metrics_jsonl(os.path.join(out_root, "metrics.jsonl"))

    # -- SPMD: traced W=4 solve, merged per-worker-lane trace ---------------
    child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "spmd_obs_child.py")
    spmd = None
    try:
        proc = subprocess.run(
            [sys.executable, child, "--workers", "4", "--smoke"],
            capture_output=True, text=True, timeout=1800,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-2000:])
        spmd = json.loads(proc.stdout)
        if not spmd["bitwise"]:
            failures.append("spmd traced result != untraced result")
        expect = ["main", "w0", "w1", "w2", "w3"]
        if spmd["lanes"] != expect:
            failures.append(f"spmd lanes {spmd['lanes']} != {expect}")
        with open(os.path.join(out_root, "fleet_trace.json"), "w") as f:
            json.dump(spmd["trace"], f)
    except Exception as e:  # noqa: BLE001 - report, don't crash the smoke
        failures.append(f"spmd series: {e}")

    # -- live telemetry: serve a few queries, scrape /metrics ---------------
    try:
        from repro.obs.live import TelemetryConfig
        from repro.serving import PMVServer, Query

        srv = PMVServer(edges, n, b=B, strategy="vertical", buckets=(4,),
                        obs=True,
                        telemetry=TelemetryConfig(latency_target_s=60.0))
        try:
            srv.serve([Query("rwr", source=i, tol=1e-6, deadline_s=120.0)
                       for i in range(3)])
            with urllib.request.urlopen(srv.telemetry.url + "/metrics",
                                        timeout=30) as resp:
                scrape = resp.read().decode()
            with open(os.path.join(out_root, "openmetrics.txt"), "w") as f:
                f.write(scrape)
            slo = srv.stats()["slo"]
            if "pmv_serve_retired_total 3.0" not in scrape:
                failures.append("openmetrics scrape missing retirements")
            if not scrape.endswith("# EOF\n"):
                failures.append("openmetrics scrape not terminated")
            if slo["latency"]["total"]["events"] != 3:
                failures.append(f"slo ledger mismatch: {slo['latency']}")
        finally:
            srv.close()
    except Exception as e:  # noqa: BLE001
        failures.append(f"telemetry scrape: {e}")

    bench = bench_obs_doc({"smoke": rec},
                          meta={"n": n, "b": B, "m": M_EDGES, "iters": ITERS},
                          extra_launches=spmd["launches"] if spmd else None,
                          fleet=spmd["fleet"] if spmd else None)
    write_bench_obs(os.path.join(out_root, "BENCH_obs.json"), bench)
    missing = ({"ell", "dense", "disk_block", "disk_io", "spmd_io",
                "spmd_overlap"} - set(bench["calibration"]))
    if missing:
        failures.append(f"calibration kinds missing: {sorted(missing)}")

    span_names = sorted({e["name"] for e in rec.events})
    report = {
        "resident_bitwise": resident_bitwise,
        "disk_bitwise": disk_bitwise,
        "spmd": ({"bitwise": spmd["bitwise"], "lanes": spmd["lanes"],
                  "trace_events": spmd["trace_events"],
                  "stragglers": spmd["fleet"]["straggler_workers"]}
                 if spmd else None),
        "trace_events": n_events,
        "span_names": span_names,
        "calibration_kinds": sorted(bench["calibration"]),
        "disk_io": {k: float(v) for k, v in d_traced.totals.items()
                    if k.startswith("store_")},
        "failures": failures,
    }
    with open(os.path.join(out_root, "parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
