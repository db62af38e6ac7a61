"""residency='disk' acceptance (ISSUE 5): out-of-core execution is bitwise
the resident engine while the live block bytes stay inside a budget the full
block set exceeds; schedule-driven prefetch overlaps I/O with compute; the
streamed horizontal gather closes the ROADMAP follow-up; manifest-backed
serving answers batched queries from disk."""
import numpy as np
import pytest

from repro.core import PMVEngine, connected_components, pagerank, sssp
from repro.graph.generators import rmat
from repro.serving import PMVServer, Query
from repro.store import DiskBlockStore, ingest_edges, open_store

pytestmark = pytest.mark.filterwarnings("error")

N, B = 256, 8


@pytest.fixture(scope="module")
def graph():
    return rmat(8, 2500, seed=17)


@pytest.fixture(scope="module")
def store_dir(graph, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("store") / "s")
    ingest_edges(graph, N, B, root, chunk_edges=333)
    return root


@pytest.fixture(scope="module")
def sym_store_dir(graph, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("store_sym") / "s")
    ingest_edges(graph, N, B, root, chunk_edges=333, symmetrize=True)
    return root


def _budget(store_dir) -> int:
    """A residency budget the FULL vertical block set exceeds but the double
    buffer fits — the paper's defining scenario (graph > memory)."""
    from repro.core import cost_model

    man = open_store(store_dir)
    total = man.total_shard_bytes("vertical")
    slice_bytes = cost_model.stripe_slice_bytes(B, man.e_cap, has_w=True)
    budget = max(3 * slice_bytes, total // 2)
    assert budget < total, "test graph too small to exceed the budget"
    return budget


@pytest.mark.parametrize("name,mk,sym", [
    ("pagerank", lambda: pagerank(N), False),
    ("sssp", lambda: sssp(0), False),
    ("cc", lambda: connected_components(), True),
])
def test_disk_vertical_bitwise_under_budget(name, mk, sym, graph, store_dir,
                                            sym_store_dir):
    """PageRank / SSSP / CC: residency='disk' == residency='device' bitwise
    on the same partition, with the resident slice bytes bounded by a budget
    the full block set exceeds (acceptance criterion)."""
    root = sym_store_dir if sym else store_dir
    budget = _budget(root)
    spec = mk()
    e_dev = PMVEngine(graph, N, b=B, strategy="vertical", symmetrize=sym)
    e_disk = PMVEngine(None, store=root, residency="disk",
                       strategy="vertical", symmetrize=sym,
                       store_budget_bytes=budget)
    r_dev = e_dev.run(mk(), max_iters=8, tol=0.0)
    r_disk = e_disk.run(spec, max_iters=8, tol=0.0)
    np.testing.assert_array_equal(r_dev.v, r_disk.v)
    assert r_disk.iterations == r_dev.iterations

    _, dstore, _v0, _ctx, _mask, meta = e_disk.prepare(spec)
    assert meta["residency"] == "disk"
    assert dstore.total_bytes > budget            # block set exceeds budget
    assert 0 < dstore.peak_resident_bytes <= budget   # ...but residency fits


@pytest.mark.parametrize("name,mk,sym", [
    ("pagerank", lambda: pagerank(N), False),
    ("sssp", lambda: sssp(0), False),
    ("cc", lambda: connected_components(), True),
])
def test_disk_matches_split_planned_resident(name, mk, sym, tmp_path):
    """A resident planned engine whose plan caps the ELL width and splits
    wider rows answers as residency='disk' does: exactly for the selection
    semirings, within float rounding for PageRank."""
    graph = rmat(8, 4000, seed=17)
    root = str(tmp_path / "s")
    ingest_edges(graph, N, B, root, chunk_edges=333, symmetrize=sym)
    e_dev = PMVEngine(graph, N, b=B, strategy="vertical", symmetrize=sym,
                      backend="auto")
    meta = e_dev.prepare(mk())[-1]
    assert meta["plan"].split and meta["split_rows"] > 0
    e_disk = PMVEngine(None, store=root, residency="disk",
                       strategy="vertical", symmetrize=sym)
    r_dev = e_dev.run(mk(), max_iters=8, tol=0.0)
    r_disk = e_disk.run(mk(), max_iters=8, tol=0.0)
    if name == "pagerank":
        np.testing.assert_allclose(r_dev.v, r_disk.v, rtol=1e-5, atol=1e-7)
    else:
        np.testing.assert_array_equal(r_dev.v, r_disk.v)


def test_disk_io_stats_and_prefetch_overlap(graph, store_dir):
    e = PMVEngine(None, store=store_dir, residency="disk", strategy="vertical")
    res = e.run(pagerank(N), max_iters=4, tol=0.0)
    rec = res.per_iter[-1]
    assert rec["store_bytes_read"] > 0
    assert rec["store_blocks_fetched"] + rec["store_blocks_skipped"] == B
    assert 0.0 <= rec["store_overlap"] <= 1.0
    assert rec["store_io_s"] >= 0.0 and rec["store_wait_s"] >= 0.0
    # per-iteration read volume matches the plan's model
    plan = e.prepare(pagerank(N))[5]["plan"]
    assert rec["store_bytes_read"] <= plan.io_bytes_per_iter()


def test_disk_skips_empty_destination_blocks(tmp_path):
    """Only destination blocks with edges are fetched: a graph whose dst ids
    all live in block 0 (ψ=cyclic: dst % b == 0) fetches exactly one block."""
    n, b = 64, 4
    rng = np.random.default_rng(0)
    src = rng.integers(0, n, 200)
    dst = 4 * rng.integers(0, n // 4, 200)
    edges = np.stack([src, dst], axis=1)
    root = str(tmp_path / "s")
    ingest_edges(edges, n, b, root)
    e = PMVEngine(None, store=root, residency="disk", strategy="vertical")
    res = e.run(pagerank(n), max_iters=2, tol=0.0)
    rec = res.per_iter[-1]
    assert rec["store_blocks_fetched"] == 1
    assert rec["store_blocks_skipped"] == b - 1
    ref = PMVEngine(edges, n, b=b, strategy="vertical").run(
        pagerank(n), max_iters=2, tol=0.0)
    np.testing.assert_array_equal(ref.v, res.v)


def test_disk_horizontal_streams_the_gather(graph, store_dir):
    """Streamed horizontal gather (ROADMAP follow-up): per-source-block scan
    from disk is bitwise the resident gather for EVERY semiring — the
    per-block contributions fold through the same pairwise tree the resident
    ``gathered_gimv`` uses, so even float plus_times is exact."""
    e_dev = PMVEngine(graph, N, b=B, strategy="horizontal")
    e_disk = PMVEngine(None, store=store_dir, residency="disk",
                       strategy="horizontal")
    r0 = e_dev.run(sssp(0), max_iters=6, tol=0.0)
    r1 = e_disk.run(sssp(0), max_iters=6, tol=0.0)
    np.testing.assert_array_equal(r0.v, r1.v)   # min_plus: exact
    r0 = e_dev.run(pagerank(N), max_iters=6, tol=0.0)
    e_disk2 = PMVEngine(None, store=store_dir, residency="disk",
                        strategy="horizontal")
    r1 = e_disk2.run(pagerank(N), max_iters=6, tol=0.0)
    np.testing.assert_array_equal(r0.v, r1.v)   # plus_times: exact too
    assert r1.per_iter[-1]["gathered_elems"] == r0.per_iter[-1]["gathered_elems"]


def test_host_residency_matches_device(graph, store_dir):
    for strategy in ("vertical", "hybrid"):
        r0 = PMVEngine(graph, N, b=B, strategy=strategy, theta=4.0).run(
            pagerank(N), max_iters=5, tol=0.0)
        r1 = PMVEngine.from_store(store_dir, strategy=strategy, theta=4.0).run(
            pagerank(N), max_iters=5, tol=0.0)
        np.testing.assert_array_equal(r0.v, r1.v)


def test_explain_reports_disk_residency(store_dir):
    eng = PMVEngine(None, store=store_dir, residency="disk",
                    strategy="vertical")
    report = eng.explain(pagerank(N))
    assert "residency=disk" in report
    assert "disk I/O" in report


def test_disk_serving_from_manifest_path(graph, store_dir):
    """PMVServer accepts a manifest path; disk-residency batched serving is
    bitwise the edges-based server (vertical compact path)."""
    queries = [Query(spec_kind="pagerank"), Query(spec_kind="sssp", source=3),
               Query(spec_kind="sssp", source=11)]
    s_disk = PMVServer(store=store_dir, residency="disk", strategy="vertical")
    s_edges = PMVServer(graph, N, b=B, strategy="vertical")
    r1 = s_disk.serve(list(queries))
    r0 = s_edges.serve(list(queries))
    for a, c in zip(r0, r1):
        np.testing.assert_array_equal(a.vector, c.vector)
        assert a.iterations == c.iterations


def test_disk_overflow_falls_back_to_structural_capacity(tmp_path):
    """A too-tight model capacity overflows out of core too; the disk
    engine's retry is the structural capacity (its compact exchange has no
    dense variant), not the resident path's dense exchange."""
    from repro.graph.generators import star_graph

    n, b = 64, 4
    edges = star_graph(n)
    root = str(tmp_path / "s")
    ingest_edges(edges, n, b, root)
    eng = PMVEngine(None, store=root, residency="disk", strategy="vertical",
                    capacity="model", slack=0.01)
    res = eng.run(pagerank(n), max_iters=6, tol=0.0)
    assert res.totals["fallback"] == "structural_capacity"
    ref = PMVEngine(edges, n, b=b, strategy="vertical").run(
        pagerank(n), max_iters=6, tol=0.0)
    np.testing.assert_array_equal(ref.v, res.v)


def test_host_residency_keeps_stripes_on_host(graph, store_dir):
    """residency='host' leaves the matrix pytree as numpy (the jitted step
    pulls it per call); 'device' commits jnp arrays."""
    import jax.numpy as jnp

    e_host = PMVEngine.from_store(store_dir, strategy="vertical")
    _, m_host, *_ = e_host.prepare(pagerank(N))
    assert isinstance(m_host["stripe"].seg_local, np.ndarray)
    e_dev = PMVEngine(None, store=store_dir, residency="device",
                      strategy="vertical")
    _, m_dev, *_ = e_dev.prepare(pagerank(N))
    assert isinstance(m_dev["stripe"].seg_local, jnp.ndarray)


def test_disk_unsupported_configurations_raise(graph, store_dir):
    # hybrid out of core needs the θ-split shards ingest_edges(theta=...)
    # writes; a theta-less store names the re-ingest precisely.
    with pytest.raises(ValueError, match="re-ingest"):
        PMVEngine(None, store=store_dir, residency="disk",
                  strategy="hybrid", theta=4.0).prepare(pagerank(N))
    with pytest.raises(ValueError, match="pallas"):
        PMVEngine(None, store=store_dir, residency="disk",
                  strategy="vertical", backend="pallas").prepare(pagerank(N))
    with pytest.raises(ValueError, match="exchange"):
        PMVEngine(None, store=store_dir, residency="disk",
                  strategy="vertical", exchange="dense").prepare(pagerank(N))
    with pytest.raises(ValueError, match="budget"):
        DiskBlockStore(open_store(store_dir), "vertical", pagerank(N),
                       budget_bytes=8)


@pytest.fixture(scope="module")
def hybrid_store_dir(graph, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("store_hyb") / "s")
    ingest_edges(graph, N, B, root, chunk_edges=333, theta=4.0)
    return root


@pytest.mark.parametrize("name,mk", [
    ("pagerank", lambda: pagerank(N)),
    ("sssp", lambda: sssp(0)),
])
def test_disk_hybrid_bitwise(name, mk, graph, hybrid_store_dir):
    """strategy='hybrid' under residency='disk' runs from the θ-split shards
    and is bitwise the resident hybrid step (sparse compact exchange +
    streamed dense gather, combined elementwise)."""
    spec = mk()
    r0 = PMVEngine(graph, N, b=B, strategy="hybrid", theta=4.0).run(
        mk(), max_iters=6, tol=0.0)
    eng = PMVEngine(None, store=hybrid_store_dir, residency="disk",
                    strategy="hybrid", theta=4.0)
    r1 = eng.run(spec, max_iters=6, tol=0.0)
    np.testing.assert_array_equal(r0.v, r1.v)
    rec = r1.per_iter[-1]
    assert rec["store_bytes_read"] > 0
    assert rec["gathered_elems"] > 0 and rec["exchanged_elems"] > 0
    # both legs' I/O is accounted: fetched + skipped spans BOTH stripings
    assert rec["store_blocks_fetched"] + rec["store_blocks_skipped"] == 2 * B


def test_disk_hybrid_theta_must_match_store(hybrid_store_dir):
    with pytest.raises(ValueError, match="does not match"):
        PMVEngine(None, store=hybrid_store_dir, residency="disk",
                  strategy="hybrid", theta=9.0).prepare(pagerank(N))


def test_disk_launch_order_is_bitwise_irrelevant(graph, store_dir,
                                                 hybrid_store_dir):
    """Reversing the prefetch launch schedule cannot change the result: the
    streamed folds key every contribution by block index and reduce through
    the fixed pairwise tree, never in arrival order (regression for the
    order-independent fold)."""
    spec = pagerank(N)
    base = PMVEngine(None, store=store_dir, residency="disk",
                     strategy="horizontal").run(spec, max_iters=5, tol=0.0)
    eng = PMVEngine(None, store=store_dir, residency="disk",
                    strategy="horizontal")
    ex = eng.prepare(spec)[5]["executor"]
    ex.schedule = list(reversed(ex.schedule))
    rev = eng.run(spec, max_iters=5, tol=0.0)
    np.testing.assert_array_equal(base.v, rev.v)

    base = PMVEngine(None, store=hybrid_store_dir, residency="disk",
                     strategy="hybrid", theta=4.0).run(spec, max_iters=5, tol=0.0)
    eng = PMVEngine(None, store=hybrid_store_dir, residency="disk",
                    strategy="hybrid", theta=4.0)
    ex = eng.prepare(spec)[5]["executor"]
    ex.schedule = list(reversed(ex.schedule))
    ex.dense_schedule = list(reversed(ex.dense_schedule))
    rev = eng.run(spec, max_iters=5, tol=0.0)
    np.testing.assert_array_equal(base.v, rev.v)
