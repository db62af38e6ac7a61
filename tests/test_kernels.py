"""Pallas kernel validation: interpret-mode vs pure-jnp oracles over
shape/dtype/semiring sweeps (per-kernel allclose requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_gimv import dense_gimv, dense_gimv_multi, dense_gimv_multi_ref, dense_gimv_ref
from repro.kernels.ell_spmv import (ell_from_edges, ell_gimv, ell_gimv_multi,
                                    ell_gimv_multi_ref, ell_gimv_ref)

SEMIRINGS = ["plus_times", "min_plus", "min_src", "max_plus"]
DENSE_SHAPES = [(128, 128), (256, 384), (100, 200), (1, 1), (129, 257), (512, 64)]
MULTI_SHAPES = [(128, 128, 128), (256, 384, 17), (100, 200, 33), (1, 1, 1), (129, 257, 8), (512, 64, 2)]


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("shape", DENSE_SHAPES)
def test_dense_gimv_matches_ref(semiring, shape):
    M, K = shape
    rng = np.random.default_rng(hash((semiring, shape)) % 2**31)
    m = rng.random((M, K)).astype(np.float32)
    if semiring == "min_src":
        m = (m > 0.7).astype(np.float32)
    v = rng.random(K).astype(np.float32)
    got = dense_gimv(jnp.asarray(m), jnp.asarray(v), semiring=semiring, interpret=True)
    want = dense_gimv_ref(jnp.asarray(m), jnp.asarray(v), semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_dense_gimv_min_src_dtypes(dtype):
    """CC labels are int32; min_src must work for both dtypes."""
    rng = np.random.default_rng(0)
    m = (rng.random((64, 96)) > 0.8).astype(np.float32)
    v = rng.integers(0, 100, 96).astype(dtype) if dtype == np.int32 else rng.random(96).astype(dtype)
    got = dense_gimv(jnp.asarray(m), jnp.asarray(v), semiring="min_src", interpret=True)
    want = dense_gimv_ref(jnp.asarray(m), jnp.asarray(v), semiring="min_src")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dense_gimv_plus_times_equals_matvec():
    rng = np.random.default_rng(1)
    m = rng.random((200, 300)).astype(np.float32)
    v = rng.random(300).astype(np.float32)
    got = dense_gimv(jnp.asarray(m), jnp.asarray(v), semiring="plus_times", interpret=True)
    np.testing.assert_allclose(np.asarray(got), m @ v, rtol=1e-5)


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("shape", MULTI_SHAPES)
def test_dense_gimv_multi_matches_vmapped_ref(semiring, shape):
    """The [M,K]x[K,Q] multi-query kernel vs the vmapped single-query oracle
    (interpret mode), all four semirings, ragged shapes included."""
    M, K, Q = shape
    rng = np.random.default_rng(hash(("multi", semiring, shape)) % 2**31)
    m = rng.random((M, K)).astype(np.float32)
    if semiring == "min_src":
        m = (m > 0.7).astype(np.float32)
    v = rng.random((K, Q)).astype(np.float32)
    got = dense_gimv_multi(jnp.asarray(m), jnp.asarray(v), semiring=semiring, interpret=True)
    want = dense_gimv_multi_ref(jnp.asarray(m), jnp.asarray(v), semiring=semiring)
    assert got.shape == (M, Q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_dense_gimv_multi_q1_equals_single(semiring):
    """Q=1 must reduce to the single-vector kernel exactly."""
    rng = np.random.default_rng(7)
    m = rng.random((96, 160)).astype(np.float32)
    if semiring == "min_src":
        m = (m > 0.8).astype(np.float32)
    v = rng.random(160).astype(np.float32)
    multi = dense_gimv_multi(jnp.asarray(m), jnp.asarray(v)[:, None], semiring=semiring, interpret=True)
    single = dense_gimv(jnp.asarray(m), jnp.asarray(v), semiring=semiring, interpret=True)
    np.testing.assert_allclose(np.asarray(multi[:, 0]), np.asarray(single), rtol=1e-6, atol=1e-6)


def test_dense_gimv_multi_min_src_int32():
    """CC labels are int32; the multi-query presence semiring must hold them."""
    rng = np.random.default_rng(0)
    m = (rng.random((64, 96)) > 0.8).astype(np.float32)
    v = rng.integers(0, 100, (96, 5)).astype(np.int32)
    got = dense_gimv_multi(jnp.asarray(m), jnp.asarray(v), semiring="min_src", interpret=True)
    want = dense_gimv_multi_ref(jnp.asarray(m), jnp.asarray(v), semiring="min_src")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus", "min_src"])
@pytest.mark.parametrize("shape", [(100, 80, 400), (300, 256, 2000), (64, 64, 0), (1, 4, 3)])
def test_ell_gimv_matches_ref(semiring, shape):
    R, N, E = shape
    rng = np.random.default_rng(hash((semiring, shape)) % 2**31)
    dst = rng.integers(0, R, E)
    src = rng.integers(0, N, E)
    w = rng.random(E).astype(np.float32)
    cols, ww = ell_from_edges(dst, src, w, R)
    v = rng.random(N).astype(np.float32)
    got = ell_gimv(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v),
                   semiring=semiring, interpret=True)
    want = ell_gimv_ref(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v), semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("shape", [(100, 80, 400, 5), (300, 256, 2000, 17),
                                   (64, 64, 0, 1), (1, 4, 3, 2), (130, 90, 900, 9)])
def test_ell_gimv_multi_matches_vmapped_ref(semiring, shape):
    """The multi-query ELL kernel ([N, Q] query-stacked vector) vs the
    vmapped single-query oracle, all four semirings, ragged shapes."""
    R, N, E, Q = shape
    rng = np.random.default_rng(hash(("ellmulti", semiring, shape)) % 2**31)
    dst = rng.integers(0, R, E)
    src = rng.integers(0, N, E)
    w = rng.random(E).astype(np.float32)
    cols, ww = ell_from_edges(dst, src, w, R)
    v = rng.random((N, Q)).astype(np.float32)
    got = ell_gimv_multi(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v),
                         semiring=semiring, interpret=True)
    want = ell_gimv_multi_ref(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v),
                              semiring=semiring)
    assert got.shape == (R, Q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
@pytest.mark.parametrize("q", [None, 3])
def test_ell_gimv_batched_tables_match_ref(semiring, q):
    """Leading axes batch tables of one shape into one launch ([*L, D, R]
    -> [*L, R(, Q)]); rows past one lane tile exercise the row tiling."""
    rng = np.random.default_rng(hash(("ellbatch", semiring, q)) % 2**31)
    L0, L1, R, N, E = 2, 3, 300, 70, 900
    tables = [ell_from_edges(rng.integers(0, R, E), rng.integers(0, N, E),
                             rng.random(E).astype(np.float32), R, d_cap=40)
              for _ in range(L0 * L1)]
    cols = np.stack([c for c, _ in tables]).reshape(L0, L1, 40, R)
    ww = np.stack([w_ for _, w_ in tables]).reshape(cols.shape)
    v = rng.random((N,) if q is None else (N, q)).astype(np.float32)
    fn, ref = (ell_gimv, ell_gimv_ref) if q is None else (ell_gimv_multi, ell_gimv_multi_ref)
    got = fn(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v),
             semiring=semiring, interpret=True)
    want = ref(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v), semiring=semiring)
    assert got.shape == (L0, L1, R) + (() if q is None else (q,))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_ell_gimv_multi_q1_equals_single(semiring):
    """Q=1 must reduce to the single-vector ELL kernel exactly."""
    rng = np.random.default_rng(13)
    R, N, E = 90, 70, 500
    dst = rng.integers(0, R, E)
    src = rng.integers(0, N, E)
    w = rng.random(E).astype(np.float32)
    cols, ww = ell_from_edges(dst, src, w, R)
    v = rng.random(N).astype(np.float32)
    multi = ell_gimv_multi(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v)[:, None],
                           semiring=semiring, interpret=True)
    single = ell_gimv(jnp.asarray(cols), jnp.asarray(ww), jnp.asarray(v),
                      semiring=semiring, interpret=True)
    np.testing.assert_allclose(np.asarray(multi[:, 0]), np.asarray(single),
                               rtol=1e-6, atol=1e-6)


def test_ell_gimv_multi_min_src_int32():
    """CC labels are int32; the multi-query src semiring must carry them."""
    rng = np.random.default_rng(3)
    R, N, E = 60, 60, 250
    dst = rng.integers(0, R, E)
    src = rng.integers(0, N, E)
    cols, _ = ell_from_edges(dst, src, None, R)
    v = rng.integers(0, 100, (N, 4)).astype(np.int32)
    got = ell_gimv_multi(jnp.asarray(cols), None, jnp.asarray(v),
                         semiring="min_src", interpret=True)
    want = ell_gimv_multi_ref(jnp.asarray(cols), None, jnp.asarray(v), semiring="min_src")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ell_from_edges_packs_all_edges():
    """Vectorized packer: every edge lands in its destination row exactly
    once, slot order = submission order within a row."""
    dst = np.array([2, 0, 2, 2, 1])
    src = np.array([10, 11, 12, 13, 14])
    w = np.arange(5, dtype=np.float32)
    cols, ww = ell_from_edges(dst, src, w, 4)
    assert cols.shape == (3, 4)                  # slot-major [D, rows]
    np.testing.assert_array_equal(cols[:3, 2], [10, 12, 13])
    np.testing.assert_array_equal(ww[:3, 2], [0.0, 2.0, 3.0])
    np.testing.assert_array_equal(cols[:1, 0], [11])
    np.testing.assert_array_equal(cols[:, 3], [-1, -1, -1])


def test_ell_gimv_no_weights():
    """CC (min_src) never reads weights; w=None path."""
    rng = np.random.default_rng(2)
    R, N, E = 80, 80, 300
    dst = rng.integers(0, R, E)
    src = rng.integers(0, N, E)
    cols, _ = ell_from_edges(dst, src, None, R)
    v = rng.integers(0, 100, N).astype(np.int32)
    got = ell_gimv(jnp.asarray(cols), None, jnp.asarray(v), semiring="min_src", interpret=True)
    want = ell_gimv_ref(jnp.asarray(cols), None, jnp.asarray(v), semiring="min_src")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_matches_engine_dense_region():
    """The dense-region kernel computes the same sub-multiplication the
    engine's gathered path computes (PageRank semiring) on a real block."""
    from repro.core import pagerank
    from repro.core.partition import partition_graph
    from repro.graph import erdos_renyi

    n, b = 64, 2
    edges = erdos_renyi(n, 400, seed=5)
    spec = pagerank(n)
    pm, hm = partition_graph(edges, n, b, spec, theta=2.0)
    part = pm.part

    # materialize the dense region of worker 0 as a dense matrix
    stripe = hm.dense_horizontal[0]
    d_cap = hm.dense.d_cap
    dense_m = np.zeros((part.n_local, b * d_cap), np.float32)
    for jj in range(b):
        cnt = int(stripe.count[jj])
        for e in range(cnt):
            dense_m[stripe.seg_local[jj, e], jj * d_cap + stripe.gat_local[jj, e]] += stripe.w[jj, e]

    # dense sub-vector: entries of v at the dense slots
    v = np.random.default_rng(0).random(part.n_pad).astype(np.float32)
    v_blocked = part.to_blocked(v)
    v_d = np.zeros((b, d_cap), np.float32)
    for k in range(b):
        cnt = int(hm.dense.d_count[k])
        v_d[k, :cnt] = v_blocked[k, hm.dense.gather_idx[k, :cnt]]

    got = dense_gimv(jnp.asarray(dense_m), jnp.asarray(v_d.reshape(-1)),
                     semiring="plus_times", interpret=True)
    want = dense_m @ v_d.reshape(-1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)
