"""The copied plain references agree with PMVEngine at scale 10 on the CPU,
and the comparisons read what they claim."""
import numpy as np
import pytest

from _bench import CELLS, CHIPS, run, small_cell, with_devices
from bench import gen, ref


def _graph():
    return gen.graph500(10, 16, 0.57, 0.19, 0.19, 20221, 2**31 + 1, 16)


@pytest.fixture(scope="module")
def graph():
    return _graph()


def _pagerank_against_reference(cell: str) -> tuple[float, float]:
    """The engine's ranks on the cell's layout and devices against the
    reference's: the largest relative error, and the reference's sum."""
    import jax
    from repro.core import pagerank

    edges, n = _graph()
    eng = run.build_engine(small_cell(cell)["config"], edges, n,
                           jax.devices()[:CHIPS[cell]])
    got = eng.run(pagerank(n), max_iters=20, tol=0.0).v
    want = ref.pagerank(edges, n, 20)
    return ref.max_rel_err(got, want), float(want.sum())


@pytest.mark.parametrize("cell", CELLS)
def test_pagerank_reference_agrees_with_the_engine(cell):
    """On the layout of each configuration, a mesh over as many devices as
    its cell asks for."""
    err, total = with_devices(CHIPS[cell], _pagerank_against_reference, cell)
    assert err < 1e-5
    assert total <= 1.0  # sink rank leaks, as the engine's semantics say


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
