"""Packed (partition-centric) exchange suite: codec round-trips over the
fuzz harness's adversarial topologies, static-plan invariants, engine parity
against the padded sparse stream, delta iteration, the auto cost gate,
explain() rendering, serving, and the out-of-core v2 store path.

Parity contract (mirrors the repo's existing scatter-method contract,
test_planner.py): under segment scatter the packed transport is BITWISE the
compact sparse exchange for every semiring, single and batched, resident and
disk.  Under kernel scatter the exact-selection semirings stay bitwise;
plus_times matches to allclose (the one-hot dot kernels group tile
contributions differently — the same tolerance the sparse kernel path
already carries against its segment baseline).
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import PMVEngine, connected_components, cost_model, pagerank, sssp
from repro.core.engine import placement_call
from repro.core.partition import Partition
from repro.exchange import codec
from repro.exchange import plan as xplan_mod
from repro.graph import erdos_renyi, rmat
from test_fuzz_parity import SEMIRING_CASES, TOPOLOGIES, _fuzz_edges

pytestmark = pytest.mark.filterwarnings("error")


# ---------------------------------------------------------------------------
# Codec: wire (delta/bit-width) and device (uniform) forms.
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=20, deadline=None)
def test_codec_roundtrip_fuzz_topologies(data):
    """pack_ids/unpack_ids and the uniform device form round-trip the per-
    pair index sets of every adversarial topology the fuzz harness draws."""
    topology = data.draw(st.sampled_from(TOPOLOGIES), label="topology")
    b = data.draw(st.sampled_from([2, 4]), label="b")
    n = b * data.draw(st.integers(3, 12), label="n_over_b")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    rng = np.random.default_rng(seed)
    edges = _fuzz_edges(topology, n, b, rng)
    part = Partition(n=n, b=b, psi="cyclic")
    db = part.block_of(edges[:, 1])
    dl = part.local_of(edges[:, 1])
    sb = part.block_of(edges[:, 0])
    width = codec.device_width(part.n_local)
    k = 32 // width
    for i in range(b):
        for j in range(b):
            ids = np.unique(dl[(db == i) & (sb == j)]).astype(np.int64)
            pk = codec.pack_ids(ids, part.n_local)
            np.testing.assert_array_equal(codec.unpack_ids(pk), ids)
            assert codec.packed_nbytes(pk) == codec.HEADER_BYTES + 4 * pk.words.size
            p = -(-max(len(ids), 1) // k) * k
            padded = np.full(p, part.n_local, np.int64)
            padded[: len(ids)] = ids
            words = codec.pack_uniform(padded, width)
            np.testing.assert_array_equal(
                codec.unpack_uniform(words, width, p), padded)


def test_codec_edge_cases():
    n_local = 77
    for ids in ([], [0], [n_local - 1], [0, n_local - 1], list(range(n_local))):
        ids = np.asarray(ids, np.int64)
        pk = codec.pack_ids(ids, n_local)
        np.testing.assert_array_equal(codec.unpack_ids(pk), ids)
    assert codec.pack_ids([], n_local).width == 0
    with pytest.raises(ValueError, match="strictly increasing"):
        codec.pack_ids([3, 3], n_local)
    with pytest.raises(ValueError, match="out of"):
        codec.pack_ids([n_local], n_local)
    # device width must also hold the sentinel n_local itself
    assert codec.device_width(15) == 4
    assert codec.device_width(16) == 8
    assert codec.device_width((1 << 16) - 1) == 16
    assert codec.device_width(1 << 16) == 32


def test_build_exchange_invariants():
    rng = np.random.default_rng(0)
    b, n_local = 4, 24
    row_sets = [
        [np.unique(rng.integers(0, n_local, int(rng.integers(0, n_local))))
         .astype(np.int64) for _ in range(b)]
        for _ in range(b)
    ]
    plan, arrays = xplan_mod.build_exchange(row_sets, n_local, scatter="kernel")
    send, recv = arrays["send_rows"], arrays["recv_rows"]
    assert send.shape == (b, b, plan.p_dev)
    np.testing.assert_array_equal(recv, send.swapaxes(0, 1))
    rows = np.asarray(plan.pair_rows).reshape(b, b)
    off = ~np.eye(b, dtype=bool)
    assert plan.payload_slots == int(rows[off].sum())
    assert plan.p_dev >= plan.p_cap
    assert plan.p_dev % (32 // plan.width_dev) == 0
    for i in range(b):
        for j in range(b):
            ids = row_sets[i][j]
            np.testing.assert_array_equal(send[j, i, : len(ids)], ids)
            assert (send[j, i, len(ids):] == n_local).all()
    decoded = codec.unpack_uniform(
        arrays["recv_words"].reshape(b, b, -1), plan.width_dev, plan.p_dev)
    np.testing.assert_array_equal(decoded, recv)


# ---------------------------------------------------------------------------
# Engine parity: packed vs padded sparse stream.
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=12, deadline=None)
def test_engine_packed_matches_sparse_fuzz(data):
    """Bitwise sparse == packed under segment scatter, for every semiring,
    over the adversarial topology pool, vertical and hybrid."""
    semiring = data.draw(st.sampled_from(sorted(SEMIRING_CASES)), label="semiring")
    topology = data.draw(st.sampled_from(TOPOLOGIES), label="topology")
    strategy = data.draw(st.sampled_from(["vertical", "hybrid"]), label="strategy")
    b = data.draw(st.sampled_from([2, 4]), label="b")
    n = b * data.draw(st.integers(3, 10), label="n_over_b")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    edges = _fuzz_edges(topology, n, b, np.random.default_rng(seed))
    mk, sym, _exact = SEMIRING_CASES[semiring]
    spec = mk(n)
    kw = dict(b=b, strategy=strategy, theta=3.0, symmetrize=sym,
              scatter="segment")
    rs = PMVEngine(edges, n, exchange="sparse", **kw).run(
        spec, max_iters=3, tol=0.0)
    rp = PMVEngine(edges, n, exchange="packed", **kw).run(
        spec, max_iters=3, tol=0.0)
    np.testing.assert_array_equal(np.asarray(rs.v), np.asarray(rp.v))


def test_engine_packed_kernel_scatter():
    """Kernel scatter: exact-selection semirings stay bitwise; plus_times
    matches to the same tolerance the sparse kernel path already carries."""
    n, b = 96, 4
    edges = erdos_renyi(n, 420, seed=3)
    kw = dict(b=b, strategy="vertical", backend="auto", scatter="kernel")
    rs = PMVEngine(edges, n, exchange="sparse", **kw).run(
        sssp(0), max_iters=4, tol=0.0)
    rp = PMVEngine(edges, n, exchange="packed", **kw).run(
        sssp(0), max_iters=4, tol=0.0)
    np.testing.assert_array_equal(np.asarray(rs.v), np.asarray(rp.v))
    rs = PMVEngine(edges, n, exchange="sparse", **kw).run(
        pagerank(n), max_iters=4, tol=0.0)
    rp = PMVEngine(edges, n, exchange="packed", **kw).run(
        pagerank(n), max_iters=4, tol=0.0)
    np.testing.assert_allclose(np.asarray(rs.v), np.asarray(rp.v),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("graph,backend,stream", [
    ("erdos_renyi", "xla", "auto"),
    ("split_rows", "auto", "on"),
    ("split_rows", "auto", "off"),
])
@pytest.mark.parametrize("strategy", ["vertical", "hybrid"])
def test_packed_batched_matches_sparse(strategy, graph, backend, stream):
    """Trailing-Q batches: bitwise parity, and the packed wire model charges
    Q values per slot with no per-iteration id leg.  The planned cases run a
    plan that splits wide ELL rows (streamed payload vs streamed compact,
    and the fused layout)."""
    b, q = 4, 5
    if graph == "erdos_renyi":
        n, edges = 96, erdos_renyi(96, 420, seed=3)
    else:
        n, edges = 128, rmat(7, 1500, seed=11)
    spec = pagerank(n)
    outs = {}
    for xch in ("sparse", "packed"):
        eng = PMVEngine(edges, n, b=b, strategy=strategy, theta=4.0,
                        exchange=xch, scatter="segment", backend=backend,
                        stream=stream)
        _, matrix, _v0, _ctx, mask, meta = eng.prepare(spec)
        if graph == "split_rows":
            assert meta["plan"].split and meta["split_rows"] > 0
            assert meta["plan"].stream == stream
        rng = np.random.default_rng(0)
        vb = jnp.asarray(
            rng.random((b, meta["part"].n_local, q)).astype(np.float32))
        v_new, _r, stats = placement_call(
            spec, meta["cfg"], matrix, vb, {}, mask, None)
        outs[xch] = (np.asarray(v_new), stats, meta)
    np.testing.assert_array_equal(outs["sparse"][0], outs["packed"][0])
    xp = outs["packed"][2]["cfg"].xplan
    assert float(outs["packed"][1]["exchange_payload_bytes"]) == \
        xp.payload_slots * q * 4
    assert float(outs["packed"][1]["exchange_id_bytes"]) == xp.id_bytes


def test_serving_packed_matches_sparse():
    """The packed transport flows through the serving tier's batched Q
    payloads unchanged (the server never threads delta state)."""
    from repro.serving import PMVServer, Query

    n, b = 128, 4
    edges = erdos_renyi(n, 600, seed=9)
    res = {}
    for xch in ("sparse", "packed"):
        srv = PMVServer(edges, n, b=b, strategy="vertical", exchange=xch,
                        buckets=(4,), max_iters=60)
        res[xch] = srv.serve([Query("rwr", source=s, tol=1e-7)
                              for s in (1, 5, 11)])
    for rs, rp in zip(res["sparse"], res["packed"]):
        np.testing.assert_array_equal(np.asarray(rs.vector),
                                      np.asarray(rp.vector))


# ---------------------------------------------------------------------------
# Delta iteration.
# ---------------------------------------------------------------------------

def test_delta_eps0_bitwise():
    """eps=0 ships exactly the rows whose payload bits changed — provably
    lossless, so the solve is bitwise the full-stream packed run."""
    n, b = 96, 4
    edges = erdos_renyi(n, 420, seed=1)
    spec = pagerank(n)
    kw = dict(b=b, strategy="vertical", exchange="packed", scatter="segment")
    rf = PMVEngine(edges, n, **kw).run(spec, max_iters=6, tol=0.0)
    rd = PMVEngine(edges, n, delta_eps=0.0, **kw).run(spec, max_iters=6, tol=0.0)
    np.testing.assert_array_equal(np.asarray(rf.v), np.asarray(rd.v))
    assert "delta_sent_rows" in rd.totals
    assert "delta_sent_rows" not in rf.totals


def test_delta_decay_and_suppression():
    """On converging PageRank, per-iteration sent rows decay and the
    suppressed-row counter grows; the solution stays eps-close to the full
    stream."""
    n, b = 96, 4
    edges = erdos_renyi(n, 480, seed=2)
    spec = pagerank(n)
    kw = dict(b=b, strategy="vertical", exchange="packed", scatter="segment")
    rd = PMVEngine(edges, n, delta_eps=1e-3, **kw).run(spec, max_iters=12, tol=0.0)
    sent = [float(r["delta_sent_rows"]) for r in rd.per_iter]
    assert sent[-1] < sent[0]
    assert float(rd.totals["delta_suppressed_rows"]) > 0.0
    rf = PMVEngine(edges, n, **kw).run(spec, max_iters=12, tol=0.0)
    np.testing.assert_allclose(np.asarray(rd.v), np.asarray(rf.v), atol=5e-3)


def test_delta_gating_reasons():
    """Delta only activates where it is sound; every degradation records its
    reason for explain()."""
    n, b = 48, 4
    edges = erdos_renyi(n, 200, seed=0)
    cases = [
        (dict(strategy="vertical", exchange="sparse"), pagerank(n),
         "needs exchange='packed'"),
        (dict(strategy="hybrid", theta=4.0, exchange="packed"), pagerank(n),
         "vertical-only"),
        (dict(strategy="vertical", exchange="packed"), sssp(0),
         "exact selection"),
    ]
    for kw, spec, frag in cases:
        eng = PMVEngine(edges, n, b=b, delta_eps=1e-4, **kw)
        *_, meta = eng.prepare(spec)
        assert meta["delta_eps"] is None, kw
        assert frag in meta["delta_reason"], kw
    eng = PMVEngine(edges, n, b=b, strategy="vertical", exchange="packed",
                    delta_eps=1e-4)
    *_, meta = eng.prepare(pagerank(n))
    assert meta["delta_eps"] == pytest.approx(1e-4)
    assert meta["delta_reason"] == "active"


# ---------------------------------------------------------------------------
# Cost gate, wire accounting, explain.
# ---------------------------------------------------------------------------

def test_prefer_packed_exchange_gate():
    # padded: 4*3*100*(4+4) = 9600 B/iter; packed: 600*4 + 2000/10 = 2600
    assert cost_model.prefer_packed_exchange(4, 100, 600, 2000, None, 4)
    # near-empty padded stream vs an enormous one-time id shipment
    assert not cost_model.prefer_packed_exchange(2, 2, 4, 10**9, None, 4)


def test_wire_totals_id_amortization():
    """The padded stream re-pays its int32 ids every iteration; packed pays
    them once.  totals['wire_bytes'] makes the two comparable."""
    n, b, iters = 96, 4, 5
    edges = erdos_renyi(n, 420, seed=4)
    spec = pagerank(n)
    kw = dict(b=b, strategy="vertical", scatter="segment")
    rs = PMVEngine(edges, n, exchange="sparse", **kw).run(
        spec, max_iters=iters, tol=0.0)
    rp = PMVEngine(edges, n, exchange="packed", **kw).run(
        spec, max_iters=iters, tol=0.0)
    assert float(rs.totals["exchange_id_bytes"]) == pytest.approx(
        iters * float(rs.per_iter[0]["exchange_id_bytes"]))
    assert float(rp.totals["exchange_id_bytes"]) == pytest.approx(
        float(rp.per_iter[0]["exchange_id_bytes"]))
    assert float(rp.totals["wire_bytes"]) < float(rs.totals["wire_bytes"])


def test_explain_exchange_section():
    n, b = 48, 4
    edges = erdos_renyi(n, 240, seed=5)
    text = PMVEngine(edges, n, b=b, strategy="vertical",
                     exchange="packed").explain(pagerank(n))
    assert "exchange:" in text
    assert "packed (forced)" in text
    assert "payload bytes/iter" in text
    assert "per-pair rows" in text
    # a sparse prepare still renders the comparison, estimated from the
    # structural partial-nnz template
    text2 = PMVEngine(edges, n, b=b, strategy="vertical",
                      exchange="sparse").explain(pagerank(n))
    assert "exchange:" in text2
    assert "[estimated]" in text2


def test_auto_decision_recorded():
    n, b = 96, 4
    edges = erdos_renyi(n, 420, seed=6)
    eng = PMVEngine(edges, n, b=b, strategy="vertical", exchange="auto")
    *_, meta = eng.prepare(pagerank(n))
    assert meta["exchange"] in ("packed", "sparse")
    assert meta["exchange_decision"].startswith("auto:")
    r = eng.run(pagerank(n), max_iters=3, tol=0.0)
    rs = PMVEngine(edges, n, b=b, strategy="vertical", exchange="sparse",
                   scatter="segment").run(pagerank(n), max_iters=3, tol=0.0)
    np.testing.assert_allclose(np.asarray(r.v), np.asarray(rs.v),
                               rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# Out-of-core (v2 store) path.
# ---------------------------------------------------------------------------

def test_store_packed_row_sets_match_stripes(tmp_path):
    """The v2 pidx shards decode to exactly the row sets prepare() derives
    from resident stripes."""
    from repro.store import ingest_edges, load_partitioned

    n, b = 64, 4
    edges = _fuzz_edges("mixed", n, b, np.random.default_rng(7))
    man = ingest_edges(edges, n, b, os.fspath(tmp_path / "store"))
    pm, _ = load_partitioned(man, pagerank(n))
    want = xplan_mod.row_sets_from_stripes(pm.vertical, b)
    got = man.packed_row_sets()
    for i in range(b):
        for j in range(b):
            np.testing.assert_array_equal(got[i][j], want[i][j])


def test_disk_packed_parity(tmp_path):
    from repro.store import ingest_edges, verify_store

    n, b = 96, 4
    edges = erdos_renyi(n, 480, seed=3)
    man = ingest_edges(edges, n, b, os.fspath(tmp_path / "store"))
    assert man.version == 2
    assert verify_store(man).ok
    for spec in (pagerank(n), sssp(0)):
        rs = PMVEngine(None, store=man, residency="disk", strategy="vertical",
                       exchange="sparse").run(spec, max_iters=4, tol=0.0)
        rp = PMVEngine(None, store=man, residency="disk", strategy="vertical",
                       exchange="packed").run(spec, max_iters=4, tol=0.0)
        np.testing.assert_array_equal(np.asarray(rs.v), np.asarray(rp.v))
        rr = PMVEngine(edges, n=n, b=b, strategy="vertical", exchange="packed",
                       scatter="segment").run(spec, max_iters=4, tol=0.0)
        np.testing.assert_array_equal(np.asarray(rp.v), np.asarray(rr.v))
        assert float(rp.totals["wire_bytes"]) < float(rs.totals["wire_bytes"])


def test_disk_v1_store_version_gate(tmp_path):
    """A pre-packed (v1) store: forced packed raises ManifestVersionError at
    prepare() time with the re-ingest fix; auto degrades with the reason."""
    from repro.store import ManifestVersionError, ingest_edges, open_store

    n, b = 64, 4
    edges = erdos_renyi(n, 300, seed=8)
    root = tmp_path / "store"
    ingest_edges(edges, n, b, os.fspath(root))
    mpath = root / "manifest.json"
    doc = json.loads(mpath.read_text())
    doc["version"] = 1
    doc.pop("checksums", None)
    mpath.write_text(json.dumps(doc))
    man1 = open_store(os.fspath(root))
    assert not man1.has_packed_index
    with pytest.raises(ManifestVersionError, match="re-ingest"):
        PMVEngine(None, store=man1, residency="disk", strategy="vertical",
                  exchange="packed").run(pagerank(n), max_iters=1)
    eng = PMVEngine(None, store=man1, residency="disk", strategy="vertical",
                    exchange="auto")
    *_, meta = eng.prepare(pagerank(n))
    assert meta["exchange"] == "sparse"
    assert "no packed index shards" in meta["exchange_decision"]
    r = eng.run(pagerank(n), max_iters=3, tol=0.0)
    assert r.iterations == 3
