"""The copied plain references agree with PMVEngine at scale 10 on the CPU,
and the comparisons read what they claim."""
import numpy as np
import pytest

from _bench import ONE_CHIP_CELLS, run, small_cell
from bench import gen, ref


@pytest.fixture(scope="module")
def graph():
    return gen.graph500(10, 16, 0.57, 0.19, 0.19, 20221, 2**31 + 1, 16)


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_pagerank_reference_agrees_with_the_engine(graph, cell):
    """On the layout of each one-chip configuration."""
    import jax
    from repro.core import pagerank

    edges, n = graph
    eng = run.build_engine(small_cell(cell)["config"], edges, n, jax.devices()[:1])

    got = eng.run(pagerank(n), max_iters=20, tol=0.0).v
    want = ref.pagerank(edges, n, 20)
    assert ref.max_rel_err(got, want) < 1e-5
    assert want.sum() <= 1.0  # sink rank leaks, as the engine's semantics say


def test_wcc_reference_agrees_with_the_engine(graph):
    import jax
    from repro.core import connected_components

    edges, n = graph
    cell = small_cell("g500-s20-vertical.wcc")
    eng = run.build_engine(cell["config"], edges, n, jax.devices()[:1])
    res = eng.run(connected_components(), max_iters=100, tol=0.5)
    want = ref.wcc(edges, n)
    assert res.converged and ref.mismatched(res.v, want) == 0
    assert np.all(want <= np.arange(n))  # the smallest id of each component


def test_wcc_reference_by_hand():
    edges = np.array([(0, 1), (1, 0), (2, 3), (3, 2), (3, 4), (4, 3)])
    assert ref.wcc(edges, 6).tolist() == [0, 0, 2, 2, 2, 5]


def test_comparisons():
    want = np.array([1.0, 2.0, 4.0])
    assert ref.max_rel_err(np.array([1.0, 2.0, 4.4]), want) == pytest.approx(0.1)
    assert ref.mismatched(np.array([0, 0, 2]), np.array([0, 1, 2])) == 1
