"""repro.store round-trip suite (ISSUE 5): streaming ingest -> manifest ->
load must be BITWISE the in-memory ``partition_graph`` output — edges,
recomputed weights, bucketed ELL tables, and the hybrid θ-split — across
ψ ∈ {cyclic, range} and the adversarial topologies of test_fuzz_parity;
plus the chunked reader, id validation, and manifest versioning satellites.
"""
import gzip
import os

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from test_fuzz_parity import TOPOLOGIES, _fuzz_edges

from repro.core import PMVEngine, pagerank, connected_components, planner
from repro.core import blocks as blocks_lib
from repro.core.partition import partition_graph
from repro.graph import io as gio
from repro.graph.generators import rmat, symmetrize_edges
from repro.store import format as fmt
from repro.store import (
    ingest_edges,
    load_partitioned,
    open_store,
    plan_from_manifest,
)

pytestmark = pytest.mark.filterwarnings("error")


def _assert_stripes_equal(s0, s1):
    np.testing.assert_array_equal(s0.seg_local, s1.seg_local)
    np.testing.assert_array_equal(s0.gat_local, s1.gat_local)
    np.testing.assert_array_equal(s0.count, s1.count)
    if s0.w is None:
        assert s1.w is None
    else:
        np.testing.assert_array_equal(s0.w, s1.w)


def _assert_planned_equal(p0, p1):
    assert len(p0.buckets) == len(p1.buckets)
    for b0, b1 in zip(p0.buckets, p1.buckets):
        np.testing.assert_array_equal(b0.rows, b1.rows)
        np.testing.assert_array_equal(b0.cols, b1.cols)
        if b0.w is None:
            assert b1.w is None
        else:
            np.testing.assert_array_equal(b0.w, b1.w)
    assert (p0.dense is None) == (p1.dense is None)
    if p0.dense is not None:
        np.testing.assert_array_equal(p0.dense.matrix, p1.dense.matrix)
        np.testing.assert_array_equal(p0.dense.index, p1.dense.index)


def _assert_roundtrip(edges, n, b, psi, theta, spec, tmp, *, chunk, symmetrize=False):
    ref_edges = symmetrize_edges(edges) if symmetrize else edges
    pm0, hm0 = partition_graph(ref_edges, n, b, spec, psi=psi, theta=theta)
    man = ingest_edges(edges, n, b, str(tmp), psi=psi, chunk_edges=chunk,
                       symmetrize=symmetrize)
    assert man.m == len(ref_edges)
    pm1, hm1 = load_partitioned(man, spec, theta=theta)

    assert pm1.part == pm0.part
    np.testing.assert_array_equal(pm1.block_nnz, pm0.block_nnz)
    np.testing.assert_array_equal(pm1.partial_nnz, pm0.partial_nnz)
    assert pm1.partial_cap == pm0.partial_cap
    np.testing.assert_array_equal(pm1.stats.out_deg, pm0.stats.out_deg)
    np.testing.assert_array_equal(pm1.stats.in_deg, pm0.stats.in_deg)
    for s0, s1 in zip(pm0.vertical + pm0.horizontal,
                      pm1.vertical + pm1.horizontal):
        _assert_stripes_equal(s0, s1)

    # bucketed-ELL tables packed from the loaded stripes == packed from the
    # in-memory ones (same plan -> same tactics/boundaries on both sides).
    plan = planner.plan_execution(
        pm0, None, strategy="vertical", mode="planned",
        capacity=pm0.partial_cap, scatter="segment", stream="off")
    nl = pm0.part.n_local
    semiring = "plus_times" if spec.needs_weights else "min_src"
    for j, (s0, s1) in enumerate(zip(pm0.vertical, pm1.vertical)):
        tactics = plan.tactics_for_worker(j, "vertical")
        p0 = blocks_lib.pack_planned_stripe(
            s0, tactics, nl, layout="vertical", boundaries=plan.boundaries,
            semiring=semiring, split=plan.split)
        p1 = blocks_lib.pack_planned_stripe(
            s1, tactics, nl, layout="vertical", boundaries=plan.boundaries,
            semiring=semiring, split=plan.split)
        _assert_planned_equal(p0, p1)

    if theta is None:
        assert hm0 is None and hm1 is None
    else:
        np.testing.assert_array_equal(hm1.dense.gather_idx, hm0.dense.gather_idx)
        np.testing.assert_array_equal(hm1.dense.d_count, hm0.dense.d_count)
        assert hm1.dense.d_cap == hm0.dense.d_cap
        assert hm1.sparse_partial_cap == hm0.sparse_partial_cap
        assert (hm1.sparse_nnz, hm1.dense_nnz) == (hm0.sparse_nnz, hm0.dense_nnz)
        for s0, s1 in zip(hm0.sparse_vertical + hm0.dense_horizontal,
                          hm1.sparse_vertical + hm1.dense_horizontal):
            _assert_stripes_equal(s0, s1)
    return man


@given(data=st.data())
@settings(max_examples=6, deadline=None)
def test_roundtrip_bitwise_adversarial(data):
    """ingest -> manifest -> load == partition_graph, bitwise, across ψ,
    adversarial topologies, θ on/off, and multi-chunk streaming."""
    import tempfile

    topology = data.draw(st.sampled_from(TOPOLOGIES), label="topology")
    psi = data.draw(st.sampled_from(["cyclic", "range"]), label="psi")
    b = data.draw(st.sampled_from([2, 4]), label="b")
    n = b * data.draw(st.integers(3, 10), label="n_over_b")
    theta = data.draw(st.sampled_from([None, 1.0, 3.0, 40.0]), label="theta")
    chunk = data.draw(st.integers(1, 64), label="chunk")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    rng = np.random.default_rng(seed)
    edges = _fuzz_edges(topology, n, b, rng)
    with tempfile.TemporaryDirectory() as tmp:
        _assert_roundtrip(edges, n, b, psi, theta, pagerank(n), tmp, chunk=chunk)


@given(data=st.data())
@settings(max_examples=4, deadline=None)
def test_roundtrip_bitwise_symmetrized(data):
    """symmetrize at ingest == engine-side symmetrize_edges, bitwise (the
    streamed forward-then-reverse binning preserves dedup_edges' keep-first
    order); covers the weight-free CC spec (w is never stored or rebuilt)."""
    import tempfile

    topology = data.draw(st.sampled_from(TOPOLOGIES), label="topology")
    psi = data.draw(st.sampled_from(["cyclic", "range"]), label="psi")
    b = data.draw(st.sampled_from([2, 4]), label="b")
    n = b * data.draw(st.integers(3, 8), label="n_over_b")
    chunk = data.draw(st.integers(1, 48), label="chunk")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    rng = np.random.default_rng(seed)
    edges = _fuzz_edges(topology, n, b, rng)
    with tempfile.TemporaryDirectory() as tmp:
        _assert_roundtrip(edges, n, b, psi, None, connected_components(), tmp,
                          chunk=1 + int(chunk), symmetrize=True)


@pytest.mark.parametrize("m_edges,split", [(700, None), (1500, True)])
def test_plan_from_manifest_matches_measured(tmp_path, m_edges, split):
    """Plans rebuilt from the manifest's persisted measurements (pow2 degree
    histograms) equal plans measured from the in-memory stripes — tactics,
    bucket_rows, and costs included — also where the plan caps the ELL
    width and splits wider rows."""
    n, b = 128, 4
    edges = rmat(7, m_edges, seed=11)
    pm, _ = partition_graph(edges, n, b, pagerank(n))
    man = ingest_edges(edges, n, b, str(tmp_path / "s"), chunk_edges=101)
    for strategy in ("vertical", "horizontal"):
        cap = pm.partial_cap if strategy == "vertical" else None
        stream = "on" if strategy == "vertical" else "off"
        p0 = planner.plan_execution(
            pm, None, strategy=strategy, mode="xla", capacity=cap,
            scatter="segment", stream=stream, interpret=True, residency="disk")
        p1 = plan_from_manifest(
            man, strategy=strategy, mode="xla", capacity=cap,
            scatter="segment", stream=stream, interpret=True)
        assert p0 == p1
        assert split is None or p1.split == split
        assert p1.residency == "disk" and p1.io_bytes_per_iter() > 0


def test_engine_host_residency_bitwise(tmp_path):
    """PMVEngine.from_store (residency='host') solves bitwise like the
    edge-list engine on every strategy, hybrid included."""
    n, b = 128, 4
    edges = rmat(7, 500, seed=2)
    man = ingest_edges(edges, n, b, str(tmp_path / "s"))
    for strategy, theta in (("vertical", "auto"), ("horizontal", "auto"),
                            ("hybrid", 3.0)):
        r0 = PMVEngine(edges, n, b=b, strategy=strategy, theta=theta).run(
            pagerank(n), max_iters=5, tol=0.0)
        r1 = PMVEngine.from_store(man, strategy=strategy, theta=theta).run(
            pagerank(n), max_iters=5, tol=0.0)
        np.testing.assert_array_equal(r0.v, r1.v)


def test_ingest_memory_accounting_bounded(tmp_path):
    """The ingester's own accounting proves the bounded-memory contract:
    peak chunk + peak bin + one padded stripe, never O(|M|) rows at once."""
    n, b = 256, 8
    edges = rmat(8, 4000, seed=5)
    man = ingest_edges(edges, n, b, str(tmp_path / "s"), chunk_edges=257)
    rep = man.ingest
    assert rep["peak_chunk_rows"] <= 257
    assert rep["peak_bin_rows"] < len(edges)          # one worker's bin only
    assert rep["peak_host_rows_model"] < 2 * len(edges)


# ---------------------------------------------------------------------------
# graph.io satellites: chunked reader + id validation.
# ---------------------------------------------------------------------------

def test_iter_edges_matches_load_edges(tmp_path):
    edges = rmat(6, 300, seed=9)
    paths = {
        "npy": str(tmp_path / "e.npy"),
        "tsv": str(tmp_path / "e.tsv"),
        "gz": str(tmp_path / "e.tsv.gz"),
    }
    for p in paths.values():
        gio.save_edges(p, edges)
    for kind, p in paths.items():
        chunks = list(gio.iter_edges(p, chunk_edges=71))
        assert all(len(c) <= 71 for c in chunks)
        assert len(chunks) > 1
        np.testing.assert_array_equal(np.concatenate(chunks), edges)
        np.testing.assert_array_equal(gio.load_edges(p), edges)


def test_negative_ids_rejected(tmp_path):
    bad = np.array([[0, 1], [2, -3]], dtype=np.int64)
    p_npy = str(tmp_path / "bad.npy")
    np.save(p_npy, bad)
    with pytest.raises(ValueError, match="negative vertex id"):
        gio.load_edges(p_npy)
    with pytest.raises(ValueError, match="negative vertex id"):
        gio.infer_n(bad)
    with pytest.raises(ValueError, match="negative vertex id"):
        list(gio.iter_edges(p_npy))
    with pytest.raises(ValueError, match="negative vertex id"):
        ingest_edges(bad, 4, 2, str(tmp_path / "s"))


def test_ingest_rejects_out_of_range_ids(tmp_path):
    edges = np.array([[0, 1], [2, 9]], dtype=np.int64)
    with pytest.raises(ValueError, match="out of range"):
        ingest_edges(edges, 4, 2, str(tmp_path / "s"))


def test_ingest_from_tsv_path(tmp_path):
    edges = rmat(6, 200, seed=4)
    p = str(tmp_path / "e.tsv.gz")
    gio.save_edges(p, edges)
    man = ingest_edges(p, 64, 4, str(tmp_path / "s"), chunk_edges=53)
    pm0, _ = partition_graph(edges, 64, 4, pagerank(64))
    pm1, _ = load_partitioned(man, pagerank(64))
    for s0, s1 in zip(pm0.vertical, pm1.vertical):
        _assert_stripes_equal(s0, s1)


# ---------------------------------------------------------------------------
# Manifest versioning / validation.
# ---------------------------------------------------------------------------

def test_manifest_version_guard(tmp_path):
    import json

    edges = rmat(5, 100, seed=1)
    root = str(tmp_path / "s")
    ingest_edges(edges, 32, 2, root)
    man = open_store(root)
    assert man.version == fmt.FORMAT_VERSION
    mpath = os.path.join(root, "manifest.json")
    with open(mpath) as f:
        doc = json.load(f)
    doc["version"] = 99
    with open(mpath, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="newer than this reader"):
        open_store(root)
    doc["format"] = "something-else"
    with open(mpath, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="format"):
        open_store(root)
    with pytest.raises(FileNotFoundError, match="not a block-store"):
        open_store(str(tmp_path / "nope"))


def test_crashed_reingest_never_leaves_a_stale_manifest(tmp_path):
    """Ingest invalidates any previous manifest FIRST and writes the new one
    last (atomically), so a crash mid-re-ingest leaves a directory that
    open_store refuses — never an old manifest over new shards."""
    root = str(tmp_path / "s")
    ingest_edges(rmat(5, 100, seed=1), 32, 2, root)
    assert open_store(root).m > 0
    bad = np.array([[0, 1], [2, 99]], dtype=np.int64)   # dies in pass A
    with pytest.raises(ValueError, match="out of range"):
        ingest_edges(bad, 32, 2, root)
    with pytest.raises(FileNotFoundError, match="not a block-store"):
        open_store(root)
    # a clean re-ingest recovers the directory
    ingest_edges(rmat(5, 100, seed=1), 32, 2, root)
    assert open_store(root).m > 0


def test_missing_shard_is_a_clear_error(tmp_path):
    edges = rmat(5, 100, seed=1)
    root = str(tmp_path / "s")
    ingest_edges(edges, 32, 2, root)
    os.remove(os.path.join(root, "vertical", "w1.gat.npy"))
    with pytest.raises(FileNotFoundError, match="store shard missing"):
        load_partitioned(open_store(root), pagerank(32))


def test_engine_store_argument_validation(tmp_path):
    edges = rmat(5, 100, seed=1)
    root = str(tmp_path / "s")
    ingest_edges(edges, 32, 2, root)
    with pytest.raises(ValueError, match="not both"):
        PMVEngine(edges, 32, b=2, store=root)
    with pytest.raises(ValueError, match="does not match the store"):
        PMVEngine(None, store=root, b=4)
    with pytest.raises(ValueError, match="symmetrize"):
        PMVEngine(None, store=root, symmetrize=True)
    with pytest.raises(ValueError, match="needs store="):
        PMVEngine(edges, 32, b=2, residency="disk")


def test_explicit_psi_mismatch_raises(tmp_path):
    """psi=None means 'unspecified' (takes the store's ψ); an EXPLICIT psi
    — even the non-store default 'cyclic' — must match the manifest."""
    edges = rmat(5, 100, seed=1)
    root = str(tmp_path / "s")
    ingest_edges(edges, 32, 2, root, psi="range")
    eng = PMVEngine(None, store=root)
    assert eng.psi == "range"
    with pytest.raises(ValueError, match="psi='cyclic' does not match"):
        PMVEngine(None, store=root, psi="cyclic")


def test_weighted_columns_dropped_consistently(tmp_path):
    """'src dst weight' inputs keep the id columns in BOTH loaders (no
    reshape garbling)."""
    p_tsv = str(tmp_path / "w.tsv")
    with open(p_tsv, "w") as f:
        f.write("0\t1\t5\n2\t3\t7\n")
    p_npy = str(tmp_path / "w.npy")
    np.save(p_npy, np.array([[0, 1, 5], [2, 3, 7]], dtype=np.int64))
    want = np.array([[0, 1], [2, 3]])
    for p in (p_tsv, p_npy):
        np.testing.assert_array_equal(gio.load_edges(p), want)
        np.testing.assert_array_equal(
            np.concatenate(list(gio.iter_edges(p, chunk_edges=1))), want)
