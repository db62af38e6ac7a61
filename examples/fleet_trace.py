"""Fleet observability walkthrough: per-worker trace lanes, straggler
attribution, and live serving telemetry (repro.obs.fleet / repro.obs.live).

Runs a W=4 SPMD out-of-core PageRank with per-worker recorder shards and an
injected slow disk on worker 2, then:

    fleet_out/fleet_trace.json   merged Chrome trace — one lane per worker
                                 (open in ui.perfetto.dev; worker 2's
                                 store.fetch spans are visibly longer)
    fleet_out/fleet_report.json  the straggler report as JSON
    stdout                       fleet_report().format() — per-worker
                                 fetch/wait totals, skew, flagged stragglers

and finishes with a telemetry-enabled PMVServer: serves a few queries, then
scrapes its own OpenMetrics endpoint (the same `/metrics` a Prometheus
scraper or `repro obs top <url>` would hit).

    PYTHONPATH=src python examples/fleet_trace.py

(The emulated multi-device mesh needs XLA_FLAGS set before jax imports —
done below, so run this file directly rather than importing it.)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import json
import tempfile
import urllib.request

import numpy as np

from repro.core import PMVEngine, pagerank
from repro.core.mesh import worker_mesh
from repro.faults import FaultPlan, SlowFetch
from repro.graph import rmat
from repro.obs import (
    TelemetryConfig,
    fleet_report,
    merge_traces,
    validate_chrome_trace,
    write_fleet_report,
)
from repro.serving import PMVServer, Query
from repro.store import ingest_edges

n, b, W = 1 << 9, 8, 4
edges = rmat(9, 5_000, seed=0)
spec = pagerank(n)

store_dir = tempfile.mkdtemp(prefix="pmv_store_")
ingest_edges(edges, n, b, store_dir)
print(f"ingested {len(edges)} edges into {store_dir}")

# -- SPMD solve: W=4 workers, each with its own recorder shard; worker 2's
#    reads of block 1 are injected 100 ms slower (a failing local disk).
mesh = worker_mesh(W)
plan = FaultPlan(events=(SlowFetch(block=1, delay_s=0.1, occurrence=2,
                                   worker=2),), seed=0)
engine = PMVEngine(None, store=store_dir, residency="disk",
                   strategy="vertical", mesh=mesh, obs=True, faults=plan)
result = engine.run(spec, max_iters=6, tol=1e-6)
print(f"converged={result.converged} after {result.iterations} iterations "
      f"across {W} workers")

# the solve is bitwise the unfaulted, untraced one — tracing and the
# injected straggler only change *timing*, never bytes
clean = PMVEngine(None, store=store_dir, residency="disk",
                  strategy="vertical", mesh=mesh).run(spec, max_iters=6,
                                                      tol=1e-6)
assert np.array_equal(clean.v, result.v)

out = "fleet_out"
os.makedirs(out, exist_ok=True)

doc = merge_traces(engine.obs)          # one pid lane per worker shard
validate_chrome_trace(doc)
with open(os.path.join(out, "fleet_trace.json"), "w") as f:
    json.dump(doc, f)
lanes = [ev["args"]["name"] for ev in doc["traceEvents"]
         if ev.get("ph") == "M" and ev["name"] == "process_name"]
print(f"wrote {out}/fleet_trace.json — lanes: {lanes}")

rep = fleet_report(result)              # who was slow, and why
write_fleet_report(os.path.join(out, "fleet_report.json"), rep)
print(rep.format())

# -- live serving telemetry: rolling p99 + SLO burn over the retirement
#    ledger, scraped from the server's own OpenMetrics endpoint.
srv = PMVServer(edges, n, b=b, strategy="vertical", buckets=(4,), obs=True,
                telemetry=TelemetryConfig(latency_target_s=30.0))
try:
    srv.serve([Query("rwr", source=i, tol=1e-6, deadline_s=60.0)
               for i in range(4)])
    with urllib.request.urlopen(srv.telemetry.url + "/metrics") as resp:
        scrape = resp.read().decode()
    slo_lines = [l for l in scrape.splitlines() if l.startswith("pmv_slo")]
    print(f"\nscraped {srv.telemetry.url}/metrics "
          f"({len(scrape.splitlines())} lines); SLO gauges:")
    print("\n".join(f"  {l}" for l in slo_lines[:8]))
    print(f"\nstats()['slo'] latency burn (total): "
          f"{srv.stats()['slo']['latency']['total']['burn_rate']}")
finally:
    srv.close()
