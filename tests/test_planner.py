"""Per-block execution planner: ExecutionPlan classification, row-bucketed
ELL packing, planned execution parity and the scatter-combine kernel.

Acceptance (ISSUE 3): planned execution (backend='auto' -> mode='planned')
is numerically identical to the forced-global baselines — for all four
kernel semirings x {single, batched Q} x {emulation, shard_map}, the planner
output matches backend='xla' and backend='pallas' results (exact for the
selection semirings, allclose for plus_times whose reduction order moves).
"""
import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import PMVEngine, connected_components, pagerank, planner, sssp
from repro.core import blocks as blocks_lib
from repro.core.engine import placement_call
from repro.core.gimv import GimvSpec
from repro.core.sparse_exchange import scatter_partials
from repro.core.partition import partition_graph
from repro.graph import erdos_renyi, rmat
from test_fuzz_parity import TOPOLOGIES, _fuzz_edges

# Planner/fuzz suites run with warnings promoted to errors (CI gate).
pytestmark = pytest.mark.filterwarnings("error")

STRATEGIES = ["horizontal", "vertical", "hybrid"]


def _max_plus_spec(n):
    return GimvSpec(
        name="maxplus", combine2="add", combine_all="max", dtype=np.float32,
        assign=lambda v, r, ctx: jnp.maximum(v, r),
        init=lambda ids, ctx: np.zeros(ids.shape, np.float32),
    )


# (spec factory, needs symmetrize, exact integer/selection semiring?)
SEMIRING_CASES = {
    "plus_times": (pagerank, False, False),
    "min_plus": (lambda n: sssp(0), False, True),
    "min_src": (lambda n: connected_components(), True, True),
    "max_plus": (_max_plus_spec, False, True),
}


def _tactic_mix_edges(n: int = 64, b: int = 4) -> np.ndarray:
    """A graph whose plan exercises ALL THREE tactics with psi='cyclic':
    a clique over the vertices congruent 0 mod b (one fully dense block),
    a ring (every block pair touched sparsely is NOT true — the ring only
    hits (i, i) and (i, i+1) pairs, leaving the rest structurally empty)."""
    ids0 = np.arange(0, n, b)
    clique = np.array([(s, d) for s in ids0 for d in ids0])
    ring = np.stack([np.arange(n), (np.arange(n) + 1) % n], axis=1)
    return np.concatenate([clique, ring])


def _split_rows_edges(n: int = 128, b: int = 4) -> np.ndarray:
    """An R-MAT graph whose skewed in-degrees make the planner cap the ELL
    width (plan.split): a few rows per block are wider than K."""
    return rmat(7, 1500, seed=11)


# test graphs: (n, edge factory)
GRAPHS = {"tactic_mix": (64, _tactic_mix_edges),
          "split_rows": (128, _split_rows_edges)}


def _assert_split(meta):
    assert meta["plan"].split and meta["split_rows"] > 0
    assert meta["ell_cap"] == meta["plan"].boundaries[-1]


def _rand_v(spec, shape, rng, n):
    if np.dtype(spec.dtype) == np.int32:
        return jnp.asarray(rng.integers(0, n, shape).astype(np.int32))
    return jnp.asarray(rng.random(shape).astype(np.float32))


def _assert_close(exact, got, want):
    if exact:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Planner classification.
# ---------------------------------------------------------------------------

def test_plan_tactics_cover_skip_ell_dense():
    n, b = 64, 4
    eng = PMVEngine(_tactic_mix_edges(n, b), n, b=b, strategy="vertical",
                    backend="auto")
    _, matrix, _v0, _ctx, _mask, meta = eng.prepare(pagerank(n))
    plan = meta["plan"]
    assert meta["backend"] == "planned" and plan.mode == "planned"
    counts = plan.tactic_counts()
    assert counts["skip"] > 0 and counts["ell"] > 0 and counts["dense"] > 0
    # the clique block (0, 0) is the dense one; empty blocks are skipped
    assert plan.block(0, 0).tactic == "dense"
    for bp in plan.blocks:
        assert (bp.tactic == "skip") == (bp.nnz == 0)
    assert "planned" in matrix
    # the plan is static + hashable (jit closes over StepConfig carrying it)
    assert hash(plan) == hash(meta["cfg"].plan)


def test_plan_built_for_forced_backends_too():
    """Forced 'xla'/'pallas' remain overrides, but still carry the measured
    tactic table for explain()."""
    n = 64
    edges = erdos_renyi(n, 300, seed=1)
    for be, mode in [("xla", "xla"), ("pallas", "pallas")]:
        eng = PMVEngine(edges, n, b=4, strategy="vertical", backend=be)
        _, matrix, _v0, _ctx, _mask, meta = eng.prepare(pagerank(n))
        assert meta["plan"].mode == mode
        assert len(meta["plan"].blocks) == 16
        assert "planned" not in matrix


def test_auto_backend_without_kernel_semiring_falls_back_to_xla():
    n = 64
    spec = GimvSpec(
        name="mulmin", combine2="mul", combine_all="min", dtype=np.float32,
        assign=lambda v, r, ctx: jnp.minimum(v, r),
        init=lambda ids, ctx: np.ones(ids.shape, np.float32),
    )
    eng = PMVEngine(erdos_renyi(n, 300, seed=1), n, b=4, strategy="vertical",
                    backend="auto")
    _, matrix, _v0, _ctx, _mask, meta = eng.prepare(spec)
    assert meta["backend"] == "xla"
    assert "planned" not in matrix


def test_bucket_boundaries_power_of_two_capped():
    assert planner.bucket_boundaries(1) == (1,)
    assert planner.bucket_boundaries(5) == (1, 2, 4, 5)
    assert planner.bucket_boundaries(64) == (1, 2, 4, 8, 16, 32, 64)
    bs = planner.bucket_boundaries(4096, max_buckets=4)
    assert len(bs) == 4 and bs[-1] == 4096
    # a cap K < d_max stops the widths at K (wider rows split)
    assert planner.bucket_boundaries(4096, cap=16) == (1, 2, 4, 8, 16)
    assert planner.bucket_boundaries(5, cap=1) == (1,)


def test_row_bucketing_reduces_padded_slots_on_skewed_graph():
    """The acceptance claim fig10 also benchmarks: on a power-law-ish graph
    (star + ring) the bucketed slices pad far fewer slots than one d_cap."""
    from repro.graph import star_graph

    n = 256
    edges = np.concatenate([
        star_graph(n),
        np.stack([np.arange(n), (np.arange(n) + 1) % n], axis=1)])
    eng = PMVEngine(edges, n, b=4, strategy="vertical", backend="auto")
    _, matrix, _v0, _ctx, _mask, meta = eng.prepare(pagerank(n))
    plan = meta["plan"]
    assert plan.planned_slots < plan.flat_padded_slots
    # measure the actually packed tables, not just the plan's estimate
    planned = matrix["planned"]
    bucketed_slots = sum(int(np.asarray(b_.cols).size) for b_ in planned.buckets)
    flat = blocks_lib.stack_ells([
        blocks_lib.stripe_to_ell(s, meta["part"].n_local) for s in meta["pm"].vertical])
    assert bucketed_slots < int(np.asarray(flat.cols).size)


# ---------------------------------------------------------------------------
# Parity: planned == xla == pallas (emulation; shard_map below).
# ---------------------------------------------------------------------------

def _prep(strategy, semiring, backend, edges, n, b=4):
    mk, sym, _ = SEMIRING_CASES[semiring]
    spec = mk(n)
    eng = PMVEngine(edges, n, b=b, strategy=strategy, theta=40.0,
                    symmetrize=sym, backend=backend)
    _, matrix, _v0, _ctx, mask, meta = eng.prepare(spec)
    return spec, matrix, mask, meta


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("semiring", sorted(SEMIRING_CASES))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_planned_step_matches_forced_backends(strategy, semiring, graph):
    """Single + batched steps on a graph whose plan mixes all three tactics,
    and on one whose plan caps the ELL width and splits wider rows (fused
    vertical, hybrid and horizontal merged layouts)."""
    b = 4
    n, make_edges = GRAPHS[graph]
    edges = make_edges(n, b)
    _, _, exact = SEMIRING_CASES[semiring]
    outs = {}
    for be in ("xla", "pallas", "auto"):
        spec, matrix, mask, meta = _prep(strategy, semiring, be, edges, n, b)
        if be == "auto":
            assert meta["backend"] == "planned"
            counts = meta["plan"].tactic_counts()
            if graph == "tactic_mix":
                assert counts["dense"] > 0 and counts["skip"] > 0
            else:
                _assert_split(meta)
        rng = np.random.default_rng(0)
        nl = meta["part"].n_local
        for q in (None, 3):
            shape = (b, nl) if q is None else (b, nl, q)
            v = _rand_v(spec, shape, rng, n)
            o, _r, _s = placement_call(spec, meta["cfg"], matrix, v, {}, mask, None)
            outs[(be, q)] = o
    for q in (None, 3):
        _assert_close(exact, outs[("auto", q)], outs[("xla", q)])
        _assert_close(exact, outs[("auto", q)], outs[("pallas", q)])


@pytest.mark.parametrize("exchange", ["sparse", "dense"])
def test_planned_vertical_exchanges_match_xla(exchange):
    n = 96
    edges = erdos_renyi(n, 420, seed=3)
    spec = pagerank(n)
    outs = {}
    for be in ("xla", "auto"):
        eng = PMVEngine(edges, n, b=4, strategy="vertical", exchange=exchange,
                        backend=be)
        r = eng.run(spec, max_iters=10, tol=0.0)
        outs[be] = r.v
    np.testing.assert_allclose(outs["auto"], outs["xla"], rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_run_parity_planned(strategy):
    """Full solves converge identically (iterations + vector) under the plan."""
    n = 96
    edges = erdos_renyi(n, 420, seed=3)
    kw = dict(b=4, strategy=strategy, theta=4.0)
    rx = PMVEngine(edges, n, **kw).run(pagerank(n), max_iters=25, tol=1e-9)
    rp = PMVEngine(edges, n, backend="auto", **kw).run(pagerank(n), max_iters=25, tol=1e-9)
    assert rx.iterations == rp.iterations
    np.testing.assert_allclose(rx.v, rp.v, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("theta,dense", [(4.0, True), (np.inf, False)])
def test_planned_hybrid_runs_dense_region_kernel_unless_empty(theta, dense):
    """The planned hybrid materializes a non-empty θ-split dense region for
    the dense kernel and skips an empty one; both match the xla solve."""
    n = 96
    edges = erdos_renyi(n, 420, seed=3)
    kw = dict(b=4, strategy="hybrid", theta=theta)
    eng = PMVEngine(edges, n, backend="auto", **kw)
    spec = pagerank(n)
    _, matrix, _v0, _ctx, _mask, meta = eng.prepare(spec)
    assert meta["backend"] == "planned"
    assert (meta["n_dense"] > 0) is dense
    assert ("dense_matrix" in matrix) is dense
    rp = eng.run(spec, max_iters=10, tol=0.0)
    rx = PMVEngine(edges, n, **kw).run(pagerank(n), max_iters=10, tol=0.0)
    np.testing.assert_allclose(rp.v, rx.v, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("graph", ["erdos_renyi", "split_rows"])
def test_serving_planned_matches_xla(graph):
    """Multi-query serving (ell_gimv_multi) under the plan == the xla
    baseline, including a plan whose wide rows split."""
    from repro.serving import PMVServer, Query

    n = 128
    edges = (erdos_renyi(n, 700, seed=9) if graph == "erdos_renyi"
             else _split_rows_edges(n))
    queries = [Query("rwr", source=s, tol=1e-7) for s in (3, 50, 101)]
    res = {}
    if graph == "split_rows":
        eng = PMVEngine(edges, n, b=4, strategy="hybrid", theta=8.0,
                        backend="auto")
        _assert_split(eng.prepare(pagerank(n))[-1])
    for be in ("xla", "auto"):
        srv = PMVServer(edges, n, b=4, strategy="hybrid", theta=8.0,
                        buckets=(4,), backend=be)
        res[be] = srv.serve([Query(q.spec_kind, source=q.source, tol=q.tol)
                             for q in queries])
    for rx, rp in zip(res["xla"], res["auto"]):
        assert rx.converged and rp.converged
        np.testing.assert_allclose(rx.vector, rp.vector, rtol=1e-5, atol=1e-7)


@pytest.mark.slow
def test_planned_spmd_matches_emulation():
    """backend='auto' under shard_map (8 fake devices) == emulation == xla,
    for all four kernel semirings (single-query engine solves)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import PMVEngine, connected_components, pagerank, sssp
from repro.core.gimv import GimvSpec
from repro.graph import erdos_renyi
n = 128
edges = erdos_renyi(n, 700, seed=21)
mesh = jax.make_mesh((8,), ("workers",))
specs = {
    "plus_times": (pagerank(n), False),
    "min_plus": (sssp(0), False),
    "min_src": (connected_components(), True),
    "max_plus": (GimvSpec(name="maxplus", combine2="add", combine_all="max",
                          dtype=np.float32,
                          assign=lambda v, r, ctx: jnp.maximum(v, r),
                          init=lambda ids, ctx: np.zeros(ids.shape, np.float32)), False),
}
for strategy in ["horizontal", "vertical", "hybrid"]:
    for name, (spec, sym) in specs.items():
        kw = dict(b=8, strategy=strategy, theta=4.0, symmetrize=sym)
        r_xla = PMVEngine(edges, n, **kw).run(spec, max_iters=6, tol=0.0)
        r_emul = PMVEngine(edges, n, backend="auto", **kw).run(spec, max_iters=6, tol=0.0)
        r_spmd = PMVEngine(edges, n, backend="auto", mesh=mesh, **kw).run(spec, max_iters=6, tol=0.0)
        np.testing.assert_allclose(r_emul.v, r_xla.v, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(r_spmd.v, r_emul.v, rtol=1e-6, atol=1e-9)
print("PLANNED-SPMD-OK")
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=560,
                         env={**os.environ, "PYTHONPATH": "src"}, cwd="/root/repo")
    assert "PLANNED-SPMD-OK" in out.stdout, (out.stdout, out.stderr[-2000:])


# ---------------------------------------------------------------------------
# Scatter-combine kernel (receive side of the sparse exchange).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("semiring,dtype", [
    ("plus_times", np.float32), ("min_plus", np.float32),
    ("max_plus", np.float32), ("min_src", np.int32)])
def test_scatter_combine_kernel_matches_ref(semiring, dtype):
    from repro.kernels.scatter_combine import (
        scatter_combine_gimv, scatter_combine_gimv_multi, scatter_combine_ref)

    rng = np.random.default_rng(0)
    n_out, t = 50, 300
    idx = jnp.asarray(rng.integers(-1, n_out + 1, t).astype(np.int32))
    if dtype == np.int32:
        val = jnp.asarray(rng.integers(0, 100, t).astype(np.int32))
        valq = jnp.asarray(rng.integers(0, 100, (t, 5)).astype(np.int32))
    else:
        val = jnp.asarray(rng.random(t).astype(np.float32))
        valq = jnp.asarray(rng.random((t, 5)).astype(np.float32))
    got = scatter_combine_gimv(idx, val, n_out, semiring=semiring, interpret=True)
    want = scatter_combine_ref(idx, val, n_out, semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    gotq = scatter_combine_gimv_multi(idx, valq, n_out, semiring=semiring, interpret=True)
    wantq = scatter_combine_ref(idx, valq, n_out, semiring=semiring)
    np.testing.assert_allclose(np.asarray(gotq), np.asarray(wantq), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lead", [(), (3,)])
@pytest.mark.parametrize("batched", [False, True])
def test_scatter_partials_kernel_method_matches_segment(lead, batched):
    """The plan's receive-side tactic table: method='kernel' == 'segment',
    including emulation leading dims and the batched (idx, val[Q]) wire."""
    spec = sssp(0)
    rng = np.random.default_rng(1)
    n_local = 33
    shape = lead + (4, 9)
    idx = jnp.asarray(rng.integers(0, n_local + 1, shape).astype(np.int32))
    vshape = shape + ((3,) if batched else ())
    val = jnp.asarray(rng.random(vshape).astype(np.float32))
    a = scatter_partials(spec, idx, val, n_local)
    k = scatter_partials(spec, idx, val, n_local, method="kernel", interpret=True)
    assert a.shape == lead + (n_local,) + ((3,) if batched else ())
    np.testing.assert_allclose(np.asarray(a), np.asarray(k), rtol=1e-6, atol=1e-7)


def test_engine_forced_kernel_scatter_matches_segment():
    n = 96
    edges = erdos_renyi(n, 420, seed=3)
    for strategy in ("vertical", "hybrid"):
        kw = dict(b=4, strategy=strategy, theta=4.0, backend="auto")
        r_seg = PMVEngine(edges, n, scatter="segment", **kw).run(
            pagerank(n), max_iters=8, tol=0.0)
        r_ker = PMVEngine(edges, n, scatter="kernel", **kw).run(
            pagerank(n), max_iters=8, tol=0.0)
        np.testing.assert_allclose(r_seg.v, r_ker.v, rtol=1e-5, atol=1e-7)


def test_forced_kernel_scatter_degrades_without_kernel_semiring():
    """A spec outside the kernel semiring table degrades scatter='kernel' to
    the segment op (mirroring the backend fallback) instead of crashing at
    trace time inside the jitted step."""
    n = 64
    spec = GimvSpec(
        name="mulmin", combine2="mul", combine_all="min", dtype=np.float32,
        assign=lambda v, r, ctx: jnp.minimum(v, r),
        init=lambda ids, ctx: np.ones(ids.shape, np.float32),
    )
    eng = PMVEngine(erdos_renyi(n, 300, seed=1), n, b=4, strategy="vertical",
                    backend="xla", scatter="kernel")
    _, _m, _v0, _c, _mask, meta = eng.prepare(spec)
    assert meta["plan"].scatter == "segment"
    r = eng.run(spec, max_iters=3, tol=0.0)  # must not raise
    assert r.iterations == 3


def test_scatter_auto_resolution():
    """'auto' is gated on the cost model's T*n_out-vs-serial-scatter
    crossover (cost_model.prefer_kernel_scatter), not a bare interpret
    flag: small receive widths take the one-hot kernel on compiled runs,
    wide outputs keep the segment op even on hardware, and interpret
    mode's slot penalty keeps the segment op on CPU hosts."""
    n = 64
    edges = erdos_renyi(n, 300, seed=1)
    eng = PMVEngine(edges, n, b=4, strategy="vertical", backend="auto")
    _, _m, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
    assert meta["plan"].scatter == "segment"  # interpret penalty on CPU
    # compiled, n_local+1 = 17 < 128 crossover: the kernel pays
    plan = planner.plan_execution(
        meta["pm"], None, strategy="vertical", mode="planned",
        capacity=meta["capacity"], scatter="auto", interpret=False)
    assert plan.scatter == "kernel"
    # compiled but WIDE output: n_local + 1 >= 128 — one-hot work loses to
    # the serial scatter even on hardware (the ROADMAP fix this pins)
    n2 = 1024
    eng2 = PMVEngine(erdos_renyi(n2, 2000, seed=2), n2, b=4,
                     strategy="vertical", backend="auto")
    _, _m2, _v02, _c2, _mask2, meta2 = eng2.prepare(pagerank(n2))
    assert meta2["part"].n_local + 1 >= 128
    plan2 = planner.plan_execution(
        meta2["pm"], None, strategy="vertical", mode="planned",
        capacity=meta2["capacity"], scatter="auto", interpret=False)
    assert plan2.scatter == "segment"
    # horizontal plans have no compact exchange to scatter
    plan3 = planner.plan_execution(
        meta["pm"], None, strategy="horizontal", mode="planned",
        capacity=None, scatter="auto", interpret=False)
    assert plan3.scatter == "segment"


# ---------------------------------------------------------------------------
# Row-bucketed ELL pack/unpack round-trip (hypothesis).
# ---------------------------------------------------------------------------

@given(data=st.data())
@settings(max_examples=25, deadline=None)
def test_bucketed_ell_roundtrip_reproduces_block_edges(data):
    """For arbitrary degree-skewed stripes, bucketed pack -> unpack is the
    identity on the edge multiset (and weights), per destination block."""
    b = data.draw(st.integers(1, 4))
    n_local = data.draw(st.integers(1, 24))
    seed = data.draw(st.integers(0, 10_000))
    skew = data.draw(st.sampled_from(["uniform", "star", "empty_blocks"]))
    rng = np.random.default_rng(seed)
    e = data.draw(st.integers(0, 120))
    if skew == "uniform":
        dst = rng.integers(0, n_local, e)
        blk = rng.integers(0, b, e)
    elif skew == "star":   # one hub row hoovers most edges: max skew
        dst = np.where(rng.random(e) < 0.8, 0, rng.integers(0, n_local, e))
        blk = rng.integers(0, b, e)
    else:                  # some inner blocks structurally empty
        dst = rng.integers(0, n_local, e)
        blk = rng.integers(0, max(b // 2, 1), e)
    src = rng.integers(0, n_local, e)
    w = rng.random(e).astype(np.float32)

    stripe, _ = blocks_lib.build_stripes(
        blk, dst, np.zeros(e, np.int64), src, w, b, stripe_axis="gat")
    stripe = stripe[0]  # worker 0 holds everything (gat_block == 0)
    d_max = 1
    cnts = np.asarray(stripe.count)
    for k in range(b):
        if cnts[k]:
            d_max = max(d_max, int(np.bincount(
                np.asarray(stripe.seg_local[k, :cnts[k]])).max()))
    boundaries = planner.bucket_boundaries(d_max)
    planned = blocks_lib.pack_planned_stripe(
        stripe, ("ell",) * b, n_local, layout="vertical",
        boundaries=boundaries, semiring="plus_times")

    got_rows, got_cols, got_w = blocks_lib.planned_to_edges(planned)
    # expected: the stripe's own edges in the flat [b * n_local] output space
    exp = []
    for k in range(b):
        cnt = int(cnts[k])
        for t in range(cnt):
            exp.append((k * n_local + int(stripe.seg_local[k, t]),
                        int(stripe.gat_local[k, t]),
                        float(stripe.w[k, t])))
    exp.sort()
    got = sorted(zip(got_rows.tolist(), got_cols.tolist(), got_w.tolist()))
    assert len(got) == len(exp)
    for (gr, gc, gw), (er, ec, ew) in zip(got, exp):
        assert (gr, gc) == (er, ec)
        np.testing.assert_allclose(gw, ew, rtol=1e-6)


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_bucketed_ell_rows_unique_and_width_bounded(data):
    """Every packed row appears in exactly one bucket, and a bucket's table
    width equals its boundary (the padding-reduction invariant)."""
    n_local = data.draw(st.integers(2, 32))
    e = data.draw(st.integers(1, 100))
    seed = data.draw(st.integers(0, 1000))
    rng = np.random.default_rng(seed)
    dst = np.where(rng.random(e) < 0.5, 0, rng.integers(0, n_local, e))
    src = rng.integers(0, n_local, e)
    deg = np.bincount(dst, minlength=n_local)
    boundaries = planner.bucket_boundaries(int(deg.max()))
    buckets = blocks_lib.pack_bucketed_ell(dst, src, None, boundaries)
    seen = []
    for k, bkt in enumerate(buckets):
        assert bkt.cols.shape[-2] == boundaries[k]
        for r, row in zip(np.asarray(bkt.rows), np.asarray(bkt.cols).T):
            assert deg[r] <= boundaries[k]
            assert int((row >= 0).sum()) == deg[r]
            seen.append(int(r))
    assert sorted(seen) == sorted(np.nonzero(deg)[0].tolist())


@given(data=st.data())
@settings(max_examples=15, deadline=None)
def test_bucketed_ell_split_rows_pack_whole_edges(data):
    """A split pack (widths capped at K) puts each row of degree d > K in
    ceil(d/K) rows of the top bucket, every piece full but the last, and
    unpacks to exactly the edges it was given."""
    n_local = data.draw(st.integers(2, 32))
    e = data.draw(st.integers(1, 120))
    cap = data.draw(st.sampled_from([1, 2, 4, 8]))
    seed = data.draw(st.integers(0, 1000))
    rng = np.random.default_rng(seed)
    dst = np.where(rng.random(e) < 0.5, 0, rng.integers(0, n_local, e))
    src = rng.integers(0, n_local, e)
    w = rng.random(e).astype(np.float32)
    deg = np.bincount(dst, minlength=n_local)
    if deg.max() <= cap:   # the widest power of two below d_max
        cap = 1 << (int(deg.max()) - 1).bit_length() - 1 if deg.max() > 1 else None
    if cap is None:
        return
    boundaries = planner.bucket_boundaries(int(deg.max()), cap=cap)
    buckets = blocks_lib.pack_bucketed_ell(dst, src, w, boundaries, split=True)
    assert [bk.split for bk in buckets] == [False] * (len(buckets) - 1) + [True]
    pieces = {}
    for k, bkt in enumerate(buckets):
        assert bkt.cols.shape[-2] == boundaries[k]
        for r, row in zip(np.asarray(bkt.rows), np.asarray(bkt.cols).T):
            pieces.setdefault(int(r), []).append(int((row >= 0).sum()))
    for r, sizes in pieces.items():
        if deg[r] > cap:
            assert sizes == [cap] * (deg[r] // cap) + ([deg[r] % cap] if deg[r] % cap else [])
        else:
            assert sizes == [deg[r]]
    planned = blocks_lib.PlannedStripe(buckets=buckets, dense=None,
                                       rows_out=n_local, layout="vertical")
    got_rows, got_cols, got_w = blocks_lib.planned_to_edges(planned)
    order = np.lexsort((src, dst))
    np.testing.assert_array_equal(got_rows, dst[order])
    np.testing.assert_array_equal(got_cols, src[order])
    np.testing.assert_allclose(np.sort(got_w), np.sort(w))
    stats = blocks_lib.ell_pack_stats(planned)
    assert stats["split_rows"] == sum(len(v) - 1 for v in pieces.values())


@given(data=st.data())
@settings(max_examples=20, deadline=None)
def test_ell_layout_never_models_worse_than_unsplit(data):
    """On the fuzz topologies and R-MAT, a plan splits rows only where its
    modelled time undercuts the unsplit widths'; otherwise it keeps the
    unsplit widths."""
    topology = data.draw(st.sampled_from(TOPOLOGIES + ("rmat",)), label="topology")
    b = data.draw(st.sampled_from([2, 4]), label="b")
    n = b * data.draw(st.integers(3, 32), label="n_over_b")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    rng = np.random.default_rng(seed)
    if topology == "rmat":
        n = 128
        edges = rmat(7, int(rng.integers(200, 2000)), seed=seed)
    else:
        edges = _fuzz_edges(topology, n, b, rng)
    pm, _ = partition_graph(edges, n, b, pagerank(n))
    plan = planner.plan_execution(pm, None, strategy="vertical", mode="planned",
                                  capacity=pm.partial_cap)
    recs = planner.measure_blocks(pm.vertical, b, stripe_axis="gat")
    hists, d_max, unsplit = planner.ell_tables(
        recs, b=b, n_local=pm.part.n_local)
    if plan.split:
        assert plan.boundaries[-1] < d_max
        assert (planner.ell_layout_seconds(hists, plan.boundaries, True)
                < planner.ell_layout_seconds(
                    hists, planner.bucket_boundaries(d_max), False))
    else:
        assert plan.boundaries == unsplit


def test_explain_and_pack_span_report_split():
    """ell_cap, ell_fill and split_rows reach explain(), the prepare
    metadata and the prepare.pack span."""
    from repro.obs import Recorder

    n = 128
    rec = Recorder()
    eng = PMVEngine(_split_rows_edges(n), n, b=4, strategy="vertical",
                    backend="auto", obs=rec)
    _, matrix, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
    _assert_split(meta)
    packed = blocks_lib.ell_pack_stats(matrix["planned"])
    assert meta["ell_fill"] == packed["ell_fill"] and 0 < meta["ell_fill"] < 1
    (span,) = rec.spans("prepare.pack")
    assert {k: span["attrs"][k] for k in ("ell_cap", "ell_fill", "split_rows")} == \
        {k: meta[k] for k in ("ell_cap", "ell_fill", "split_rows")}
    report = eng.explain(pagerank(n))
    assert f"ell_cap={meta['ell_cap']}:" in report
    assert f"split_rows={meta['split_rows']}" in report
    assert "ell_fill=" in report


# ---------------------------------------------------------------------------
# explain().
# ---------------------------------------------------------------------------

def test_explain_reports_tactics_and_padding():
    n = 64
    eng = PMVEngine(_tactic_mix_edges(n, 4), n, b=4, strategy="hybrid",
                    theta=40.0, backend="auto")
    report = eng.explain(pagerank(n))
    assert "mode=planned" in report
    assert "dense" in report and "skip" in report and "ell" in report
    assert "ELL padded slots" in report
    assert "( 0, 0)" in report  # per-block table rows


# ---------------------------------------------------------------------------
# Bucket-streamed planned execution (plan.stream, ISSUE 4 tentpole).
# ---------------------------------------------------------------------------

def test_stream_auto_keeps_fused_path_at_tiny_b():
    """b=4 / n_local=16: the materialized buffer is under the cost model's
    STREAM_MIN_SAVINGS crossover — 'auto' keeps the fused launches."""
    n = 64
    eng = PMVEngine(erdos_renyi(n, 300, seed=1), n, b=4, strategy="vertical",
                    backend="auto")
    _, matrix, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
    assert meta["plan"].stream == "off"
    assert "planned" in matrix and "streamed" not in matrix


def test_stream_auto_streams_at_large_b():
    """b=32 on a sparse graph clears the crossover: 'auto' packs the
    per-destination-block layout and the plan records stream='on'."""
    n = 2048
    eng = PMVEngine(erdos_renyi(n, 4096, seed=5), n, b=32, strategy="vertical",
                    backend="auto")
    _, matrix, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
    assert meta["plan"].stream == "on"
    assert "streamed" in matrix and "planned" not in matrix
    mp = meta["plan"].memory_profile()
    assert mp["savings"] >= 4.0


def test_stream_forced_on_degrades_where_nothing_streams():
    """The dense exchange ships full partials and horizontal never
    materializes any — a forced stream='on' resolves to 'off' there."""
    n = 64
    edges = erdos_renyi(n, 300, seed=1)
    for kw in (dict(strategy="vertical", exchange="dense"),
               dict(strategy="horizontal")):
        eng = PMVEngine(edges, n, b=4, backend="auto", stream="on", **kw)
        _, matrix, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
        assert meta["plan"].stream == "off", kw
        assert "streamed" not in matrix


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_streamed_step_bitwise_matches_materialized(graph):
    """stream='on' vs 'off' on the tactic-mix graph (all three tactics) and
    on a graph whose rows split: bitwise-identical outputs and identical
    logical/overflow counters for single and batched steps, vertical and
    hybrid (split rows combine in the same order on both schedules)."""
    b = 4
    n, make_edges = GRAPHS[graph]
    edges = make_edges(n, b)
    rng = np.random.default_rng(7)
    for strategy in ("vertical", "hybrid"):
        outs = {}
        vs = {}
        for stream in ("off", "on"):
            spec = pagerank(n)
            eng = PMVEngine(edges, n, b=b, strategy=strategy, theta=40.0,
                            backend="auto", stream=stream)
            _, matrix, _v0, _c, mask, meta = eng.prepare(spec)
            assert meta["plan"].stream == stream
            if graph == "tactic_mix":
                assert meta["plan"].tactic_counts()["dense"] > 0  # dense streamed too
            else:
                _assert_split(meta)
            nl = meta["part"].n_local
            for q in (None, 3):
                shape = (b, nl) if q is None else (b, nl, q)
                if q not in vs:
                    vs[q] = rng.random(shape).astype(np.float32)
                o, _r, s = placement_call(
                    spec, meta["cfg"], matrix, jnp.asarray(vs[q]), {}, mask, None)
                outs[(stream, q)] = (np.asarray(o), s)
        for q in (None, 3):
            off_v, off_s = outs[("off", q)]
            on_v, on_s = outs[("on", q)]
            np.testing.assert_array_equal(on_v, off_v)
            for k in ("logical_elems", "overflow"):
                assert float(np.asarray(on_s[k])) == float(np.asarray(off_s[k]))


def test_streamed_engine_run_parity():
    """Full solves under stream='on' converge identically to 'off' and to
    the forced xla baseline."""
    n = 96
    edges = erdos_renyi(n, 420, seed=3)
    for strategy in ("vertical", "hybrid"):
        kw = dict(b=4, strategy=strategy, theta=4.0)
        rx = PMVEngine(edges, n, **kw).run(pagerank(n), max_iters=25, tol=1e-9)
        r_on = PMVEngine(edges, n, backend="auto", stream="on", **kw).run(
            pagerank(n), max_iters=25, tol=1e-9)
        r_off = PMVEngine(edges, n, backend="auto", stream="off", **kw).run(
            pagerank(n), max_iters=25, tol=1e-9)
        assert rx.iterations == r_on.iterations == r_off.iterations
        np.testing.assert_array_equal(r_on.v, r_off.v)
        np.testing.assert_allclose(r_on.v, rx.v, rtol=1e-5, atol=1e-7)


def test_launch_schedule_matches_tactics_and_bucket_rows():
    """launch_schedule(worker) covers every destination block of the
    worker's stripe: entry tactic mirrors the block table, and an ell
    block's per-bucket row counts sum to its non-empty rows (what
    pack_streamed_stripe packs per scan step)."""
    n, b = 64, 4
    eng = PMVEngine(_tactic_mix_edges(n, b), n, b=b, strategy="vertical",
                    backend="auto", stream="on")
    _, _m, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
    plan = meta["plan"]
    for j in range(b):
        sched = plan.launch_schedule(j)
        assert len(sched) == b
        for i, entry in enumerate(sched):
            bp = plan.block(i, j)
            assert entry[0] == bp.tactic
            if bp.tactic == "ell":
                assert len(entry[1]) == len(plan.boundaries)
                assert sum(entry[1]) == bp.rows
            elif bp.tactic == "dense":
                assert entry[1] == plan.n_local


# ---------------------------------------------------------------------------
# format_plan / explain golden strings.
# ---------------------------------------------------------------------------

def _golden_plan():
    blocks = (
        planner.BlockPlan(i=0, j=0, tactic="dense", nnz=200, rows=16, d_max=16,
                          occupancy=0.7812, cost=32.0),
        planner.BlockPlan(i=0, j=1, tactic="ell", nnz=12, rows=8, d_max=3,
                          occupancy=0.5, cost=20.0, bucket_rows=(5, 2, 1)),
        planner.BlockPlan(i=1, j=0, tactic="skip", nnz=0, rows=0, d_max=0,
                          occupancy=0.0, cost=0.0),
        planner.BlockPlan(i=1, j=1, tactic="ell", nnz=6, rows=4, d_max=2,
                          occupancy=0.75, cost=7.0, bucket_rows=(2, 2, 0)),
    )
    return planner.ExecutionPlan(
        strategy="vertical", mode="planned", b=2, n_local=16, theta=None,
        capacity=8, boundaries=(1, 2, 4), blocks=blocks, scatter="segment",
        stream="on")


def test_format_plan_golden_header_and_tactics():
    lines = planner.format_plan(_golden_plan()).splitlines()
    assert lines[0] == ("ExecutionPlan: strategy=vertical mode=planned"
                        " capacity=8 scatter=segment stream=on")
    assert lines[1] == "  b=2 n_local=16 ell_buckets=(1, 2, 4)"
    assert lines[2] == "  tactics: skip=1 ell=2 dense=1"


def test_format_plan_golden_memory_profile_line():
    """The memory_profile line: materialized b*n_local=32 elems vs streamed
    n_local + b*cap = 32... use numbers where they differ."""
    plan = _golden_plan()
    mp = plan.memory_profile()
    assert mp == {"materialized_elems": 32, "streamed_elems": 32,
                  "savings": 1.0, "stream": "on"}
    report = planner.format_plan(plan)
    assert ("  memory profile: materialized 32 elems -> streamed 32 elems"
            " (1.00x) [stream=on]") in report
    # horizontal plans (no compact exchange, nothing to stream) omit the line
    hplan = dataclasses.replace(plan, strategy="horizontal", capacity=None)
    assert "memory profile" not in planner.format_plan(hplan)


def test_format_plan_golden_block_rows():
    report = planner.format_plan(_golden_plan())
    assert "  ( 0, 0)  dense       200     16     16  0.781         32" in report
    assert "  ( 1, 0)  skip          0      0      0  0.000          0" in report


def test_tactic_counts_invariant_sums_to_b_squared():
    """skip + ell + dense == b^2 on every prepared plan."""
    n = 64
    for strategy, edges in (("vertical", _tactic_mix_edges(n, 4)),
                            ("hybrid", _tactic_mix_edges(n, 4)),
                            ("horizontal", erdos_renyi(n, 300, seed=1))):
        eng = PMVEngine(edges, n, b=4, strategy=strategy, theta=40.0,
                        backend="auto")
        _, _m, _v0, _c, _mask, meta = eng.prepare(pagerank(n))
        counts = meta["plan"].tactic_counts()
        assert counts["skip"] + counts["ell"] + counts["dense"] == 16


def test_explain_reports_memory_profile_and_stream():
    n = 64
    eng = PMVEngine(_tactic_mix_edges(n, 4), n, b=4, strategy="vertical",
                    backend="auto", stream="on")
    report = eng.explain(pagerank(n))
    assert "stream=on" in report
    assert "memory profile: materialized" in report


@pytest.mark.slow
def test_split_rows_spmd_matches_emulation():
    """A plan that splits wide rows, under shard_map at W=4 (4 fake
    devices) == emulation: streamed compact and streamed payload (packed
    exchange), fused vertical, horizontal merged and hybrid; bitwise for
    the selection semiring, allclose for plus_times."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import PMVEngine, connected_components, pagerank
from repro.graph import rmat
n = 128
edges = rmat(7, 1500, seed=11)
mesh = jax.make_mesh((4,), ("workers",))
for strategy, stream, exchange in (("vertical", "on", "sparse"),
                                   ("vertical", "on", "packed"),
                                   ("vertical", "off", "sparse"),
                                   ("horizontal", "off", "sparse"),
                                   ("hybrid", "on", "sparse")):
    for spec, sym, exact in ((pagerank(n), False, False),
                             (connected_components(), True, True)):
        kw = dict(b=4, strategy=strategy, theta=8.0, symmetrize=sym,
                  backend="auto", stream=stream, exchange=exchange)
        e_emul = PMVEngine(edges, n, **kw)
        assert e_emul.prepare(spec)[-1]["plan"].split, (strategy, stream)
        r_emul = e_emul.run(spec, max_iters=5, tol=0.0)
        r_spmd = PMVEngine(edges, n, mesh=mesh, **kw).run(spec, max_iters=5, tol=0.0)
        r_xla = PMVEngine(edges, n, b=4, strategy=strategy, theta=8.0,
                          symmetrize=sym).run(spec, max_iters=5, tol=0.0)
        if exact:
            np.testing.assert_array_equal(r_spmd.v, r_emul.v)
            np.testing.assert_array_equal(r_emul.v, r_xla.v)
        else:
            np.testing.assert_allclose(r_spmd.v, r_emul.v, rtol=1e-6, atol=1e-9)
            np.testing.assert_allclose(r_emul.v, r_xla.v, rtol=1e-5, atol=1e-7)
print("SPLIT-SPMD-OK")
"""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=560,
                         env={**os.environ, "PYTHONPATH": "src"}, cwd=repo_root)
    assert "SPLIT-SPMD-OK" in out.stdout, (out.stdout, out.stderr[-2000:])


@pytest.mark.slow
def test_streamed_spmd_matches_emulation():
    """stream='on' under shard_map (8 fake devices) == streamed emulation ==
    fused emulation, vertical + hybrid (subprocess forces host devices)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core import PMVEngine, pagerank, sssp
from repro.graph import erdos_renyi
n = 128
edges = erdos_renyi(n, 700, seed=21)
mesh = jax.make_mesh((8,), ("workers",))
for strategy, spec in (("vertical", pagerank(n)), ("hybrid", sssp(0))):
    kw = dict(b=8, strategy=strategy, theta=4.0)
    r_off = PMVEngine(edges, n, backend="auto", stream="off", **kw).run(spec, max_iters=5, tol=0.0)
    r_on = PMVEngine(edges, n, backend="auto", stream="on", **kw).run(spec, max_iters=5, tol=0.0)
    r_spmd = PMVEngine(edges, n, backend="auto", stream="on", mesh=mesh, **kw).run(spec, max_iters=5, tol=0.0)
    np.testing.assert_array_equal(r_on.v, r_off.v)
    np.testing.assert_allclose(r_spmd.v, r_on.v, rtol=1e-6, atol=1e-9)
print("STREAMED-SPMD-OK")
"""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=560,
                         env={**os.environ, "PYTHONPATH": "src"}, cwd=repo_root)
    assert "STREAMED-SPMD-OK" in out.stdout, (out.stdout, out.stderr[-2000:])
