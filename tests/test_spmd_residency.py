"""Cross-host SPMD parity suite (the multi-host out-of-core gate).

The SPMD disk engine's contract is BITWISE: running the out-of-core solve
across W mesh workers — each owning a shard view of the store, its own
residency budget, and its own prefetch thread — produces exactly the bytes
the single-host disk executor and the fully-resident engine produce, for
every algorithm, partition function, and θ split.  The suite drives the
engine in subprocesses with ``--xla_force_host_platform_device_count`` so
the mesh has real (emulated) devices, over the adversarial topologies of
test_fuzz_parity.

Also here: the physical shard round trip (split_store -> per-shard
verify_store -> merge_stores reproduces the original store byte-for-byte,
property-tested over topology × worker count × θ) and the degraded-worker
chaos case (a broken prefetch thread on ONE worker must not change a byte).
"""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.store import (
    ingest_edges,
    merge_stores,
    open_store,
    split_store,
    verify_store,
)
from test_fuzz_parity import TOPOLOGIES, _fuzz_edges

pytestmark = pytest.mark.filterwarnings("error")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The children emulate W hosts on forced CPU devices; JAX_PLATFORMS=cpu keeps
# them off any accelerator this process may hold.
ENV = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}


def _run(script: str, timeout: int = 900) -> str:
    """Run ``script`` in a child interpreter on emulated CPU devices
    (JAX_PLATFORMS=cpu, see ENV) and return its stdout."""
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env=ENV, cwd=REPO, timeout=timeout)
    assert proc.returncode == 0, (
        f"subprocess failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc.stdout


# -- the parity grid ---------------------------------------------------------
# One subprocess per (ψ, θ) store: inside it, PageRank / CC / SSSP each run
# resident, single-host-disk, and SPMD-disk at W ∈ {1, 2, 4, 8}, all gated
# with np.array_equal.  Budgets are PER WORKER and smaller than the block
# set (the paper's graph-exceeds-memory scenario).
_PARITY = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "tests")
import tempfile
import numpy as np
import jax
from repro.core import PMVEngine, connected_components, cost_model, pagerank, sssp
from repro.core.mesh import worker_mesh
from repro.store import ingest_edges
from test_fuzz_parity import _fuzz_edges

PSI = {psi!r}
THETA_ON = {theta_on}
n, b = 240, 8
rng = np.random.default_rng(7)
edges = np.concatenate([
    _fuzz_edges(t, n, b, rng)
    for t in ("star_hub", "chain", "self_loops", "empty_stripe",
              "isolated", "multi_edge", "mixed")], axis=0)

with tempfile.TemporaryDirectory() as d:
    root = d + "/s"
    man = ingest_edges(edges, n, b, root, psi=PSI,
                       theta=4.0 if THETA_ON else None)
    e_caps = [man.e_cap_of(s) for s in man.stripings()]
    budget = 3 * cost_model.stripe_slice_bytes(b, max(e_caps), has_w=True)
    total = sum(man.total_shard_bytes(s) for s in man.stripings())
    assert budget < total, "graph too small to exceed the per-worker budget"
    for name, mk in [("pagerank", lambda: pagerank(n)),
                     ("cc", connected_components),
                     ("sssp", lambda: sssp(0))]:
        if THETA_ON:
            strategy, skw = "hybrid", dict(theta=4.0)
        elif name == "cc":
            strategy, skw = "horizontal", {{}}
        else:
            strategy, skw = "vertical", {{}}
        spec = mk()
        ref = PMVEngine(edges, n, b=b, psi=PSI, strategy=strategy, **skw).run(
            spec, max_iters=4, tol=0.0)
        single = PMVEngine.from_store(man, residency="disk", psi=PSI,
                                      strategy=strategy,
                                      store_budget_bytes=budget, **skw)
        r_single = single.run(spec, max_iters=4, tol=0.0)
        assert np.array_equal(ref.v, r_single.v), ("single", PSI, name)
        for W in (1, 2, 4, 8):
            mesh = worker_mesh(W)
            eng = PMVEngine.from_store(man, residency="disk", psi=PSI,
                                       strategy=strategy, mesh=mesh,
                                       store_budget_bytes=budget, **skw)
            r = eng.run(spec, max_iters=4, tol=0.0)
            assert np.array_equal(ref.v, r.v), ("spmd-vs-resident", PSI, name, W)
            assert np.array_equal(r_single.v, r.v), ("spmd-vs-single", PSI, name, W)
            rec = r.per_iter[-1]
            assert rec["store_bytes_read"] > 0
            if W > 1:
                for key in ("store_worker_bytes_read", "store_worker_io_s",
                            "store_worker_wait_s", "store_worker_overlap"):
                    assert len(rec[key]) == W, (key, rec[key])
        print("OK", PSI, THETA_ON, name)
print("PARITY_OK")
'''


@pytest.mark.parametrize("psi", ["cyclic", "range"])
@pytest.mark.parametrize("theta_on", [False, True])
def test_spmd_disk_bitwise_parity_grid(psi, theta_on):
    out = _run(_PARITY.format(psi=psi, theta_on=theta_on))
    assert "PARITY_OK" in out


# -- worker-count validation -------------------------------------------------
_BAD_MESH = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import numpy as np
import jax
from repro.core import PMVEngine, pagerank
from repro.core.mesh import worker_mesh
from repro.store import ingest_edges

n, b = 60, 6
rng = np.random.default_rng(0)
edges = rng.integers(0, n, size=(300, 2)).astype(np.int64)
with tempfile.TemporaryDirectory() as d:
    man = ingest_edges(edges, n, b, d + "/s")
    mesh = worker_mesh(4)   # 4 does not divide b=6
    try:
        PMVEngine.from_store(man, residency="disk", strategy="vertical",
                             mesh=mesh).prepare(pagerank(n))
    except ValueError as e:
        assert "divide" in str(e), e
        print("BAD_MESH_OK")
'''


def test_spmd_disk_mesh_must_divide_b():
    assert "BAD_MESH_OK" in _run(_BAD_MESH, timeout=300)


# -- chaos: one worker's prefetch thread dies --------------------------------
_DEGRADED = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import numpy as np
import jax
from repro.core import PMVEngine, pagerank
from repro.core.mesh import worker_mesh
from repro.faults import BreakPrefetch, FaultPlan
from repro.store import ingest_edges

n, b = 240, 8
rng = np.random.default_rng(3)
edges = rng.integers(0, n, size=(3000, 2)).astype(np.int64)
with tempfile.TemporaryDirectory() as d:
    man = ingest_edges(edges, n, b, d + "/s")
    spec = pagerank(n)
    mesh = worker_mesh(4)
    clean = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                                 mesh=mesh).run(spec, max_iters=4, tol=0.0)
    plan = FaultPlan(events=(BreakPrefetch(worker=1),), seed=0)
    eng = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                               mesh=mesh, faults=plan, obs=True)
    r = eng.run(spec, max_iters=4, tol=0.0)
    assert np.array_equal(clean.v, r.v), "degraded worker changed the result"
    inst = eng.obs.metrics.get("store.prefetch_degraded")
    assert inst is not None and float(inst.to_dict()["value"]) == 1, \
        "exactly the targeted worker should degrade"
    print("DEGRADED_OK")
'''


def test_spmd_disk_degraded_worker_still_bitwise():
    assert "DEGRADED_OK" in _run(_DEGRADED, timeout=600)


# -- fleet tracing: per-worker lanes in one merged Chrome trace --------------
_TRACED = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re
import tempfile
import numpy as np
import jax
from repro.core import PMVEngine, pagerank
from repro.core.mesh import worker_mesh
from repro.obs import (check_span_nesting, fleet_report, merge_traces,
                       validate_chrome_trace)
from repro.store import ingest_edges

n, b, W = 240, 8, 4
rng = np.random.default_rng(11)
edges = rng.integers(0, n, size=(3000, 2)).astype(np.int64)
with tempfile.TemporaryDirectory() as d:
    man = ingest_edges(edges, n, b, d + "/s")
    spec = pagerank(n)
    mesh = worker_mesh(W)
    off = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                               mesh=mesh).run(spec, max_iters=4, tol=0.0)
    eng = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                               mesh=mesh, obs=True)
    r = eng.run(spec, max_iters=4, tol=0.0)
    assert np.array_equal(off.v, r.v), "tracing changed the solve"
    doc = merge_traces(eng.obs)
    validate_chrome_trace(doc)
    check_span_nesting(doc)
    lanes = {ev["pid"]: ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "process_name"}
    worker_lanes = sorted(v for v in lanes.values() if re.fullmatch(r"w\d+", v))
    assert worker_lanes == [f"w{i}" for i in range(W)], lanes
    assert "main" in lanes.values()
    # every worker lane carries its own fetch spans, and ONLY worker lanes do
    fetch_pids = {ev["pid"] for ev in doc["traceEvents"]
                  if ev.get("ph") == "X" and ev["name"] == "store.fetch"}
    assert fetch_pids == {pid for pid, lab in lanes.items()
                          if re.fullmatch(r"w\d+", lab)}, (fetch_pids, lanes)
    rep = fleet_report(r)
    assert rep.workers == W
    assert len(rep.iterations) == r.iterations
    print("TRACED_OK")
'''


def test_spmd_disk_merged_trace_one_lane_per_worker():
    assert "TRACED_OK" in _run(_TRACED, timeout=600)


# -- straggler attribution: an injected slow disk on ONE worker --------------
_STRAGGLER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import numpy as np
import jax
from repro.core import PMVEngine, pagerank
from repro.core.mesh import worker_mesh
from repro.faults import FaultPlan, SlowFetch
from repro.obs import fleet_report
from repro.store import ingest_edges

n, b, W = 240, 8, 4
rng = np.random.default_rng(5)
edges = rng.integers(0, n, size=(3000, 2)).astype(np.int64)
with tempfile.TemporaryDirectory() as d:
    man = ingest_edges(edges, n, b, d + "/s")
    spec = pagerank(n)
    mesh = worker_mesh(W)
    clean = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                                 mesh=mesh).run(spec, max_iters=4, tol=0.0)
    plan = FaultPlan(events=(SlowFetch(block=1, delay_s=0.3, worker=2),),
                     seed=0)
    eng = PMVEngine.from_store(man, residency="disk", strategy="vertical",
                               mesh=mesh, faults=plan, obs=True)
    r = eng.run(spec, max_iters=4, tol=0.0)
    assert np.array_equal(clean.v, r.v), "slow fetch changed the result"
    rep = fleet_report(r)
    assert rep.straggler_workers == [2], rep.stragglers
    assert all(s["cause"] == "slow_fetch" for s in rep.stragglers)
    assert rep.skew["max"] > 2.0, rep.skew
    kinds = {l["kind"] for l in rep.calibration_launches()}
    assert kinds >= {"spmd_io", "spmd_overlap"}, kinds
    assert rep.format()   # renders without error
    print("STRAGGLER_OK")
'''


def test_spmd_disk_straggler_attributed_to_injected_worker():
    assert "STRAGGLER_OK" in _run(_STRAGGLER, timeout=600)


# -- physical shard round trip ----------------------------------------------
def _tree_bytes(root: str) -> dict:
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


@given(topo=st.sampled_from(TOPOLOGIES),
       count=st.sampled_from([1, 2, 4, 8]),
       theta_on=st.sampled_from([False, True]),
       seed=st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=6, deadline=None)
def test_split_merge_roundtrip_bitwise(topo, count, theta_on, seed):
    """split_store -> W self-contained shards (each passing verify_store on
    its own) -> merge_stores reproduces the original store BYTE-FOR-BYTE —
    including manifest.json, the v2 packed index shards, their digests, and
    the θ-split hybrid shards when present."""
    n, b = 96, 8
    edges = _fuzz_edges(topo, n, b, np.random.default_rng(seed))
    with tempfile.TemporaryDirectory() as d:
        root = os.path.join(d, "orig")
        ingest_edges(edges, n, b, root, theta=3.0 if theta_on else None)
        shards = split_store(root, os.path.join(d, "shards"), count)
        assert len(shards) == count
        for shard in shards:
            rep = verify_store(shard)
            assert rep.ok, rep.summary()
            assert list(shard.owned_workers()) == list(
                range(shard.worker_shard["lo"], shard.worker_shard["hi"]))
        merged_root = os.path.join(d, "merged")
        merged = merge_stores([s.root for s in shards], merged_root)
        assert merged.worker_shard is None
        assert _tree_bytes(root) == _tree_bytes(merged_root)
        assert verify_store(merged_root).ok


def test_merge_rejects_incomplete_or_foreign_shards(tmp_path):
    n, b = 64, 4
    rng = np.random.default_rng(1)
    edges = rng.integers(0, n, size=(400, 2)).astype(np.int64)
    root = str(tmp_path / "s")
    ingest_edges(edges, n, b, root)
    shards = split_store(root, str(tmp_path / "shards"), 4)
    with pytest.raises(ValueError, match="incomplete"):
        merge_stores([shards[0].root, shards[2].root], str(tmp_path / "m1"))
    # a shard of a DIFFERENT store cannot be merged in
    other_root = str(tmp_path / "other")
    ingest_edges(edges[: 200], n, b, other_root)
    other = split_store(other_root, str(tmp_path / "other_shards"), 4)
    mix = [s.root for s in shards[:3]] + [other[3].root]
    with pytest.raises(ValueError, match="different stores"):
        merge_stores(mix, str(tmp_path / "m2"))
    # re-splitting a shard is refused
    with pytest.raises(ValueError, match="shard"):
        split_store(shards[0].root, str(tmp_path / "m3"), 2)


def test_shard_view_owns_only_its_range(tmp_path):
    n, b = 64, 8
    rng = np.random.default_rng(2)
    edges = rng.integers(0, n, size=(500, 2)).astype(np.int64)
    root = str(tmp_path / "s")
    man = ingest_edges(edges, n, b, root)
    view = man.worker_shard_view(1, 4)
    assert list(view.owned_workers()) == [2, 3]
    with pytest.raises(ValueError, match="divide"):
        man.worker_shard_view(0, 3)
