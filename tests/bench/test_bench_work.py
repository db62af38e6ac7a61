"""The least work of a sweep (bench/work.py), counted by hand."""
import _bench  # noqa: F401
from bench import work

# a 6-vertex undirected graph: the path 0-1-2-3 and the edge 4-5, both
# directions of each edge listed
EDGES = [(0, 1), (1, 0), (1, 2), (2, 1), (2, 3), (3, 2), (4, 5), (5, 4)]


def test_weighted_sweep_bytes_by_hand():
    # 8 slots x (4 B id + 4 B value + 4 B weight) + 6 vertices x (4 B read + 4 B write)
    assert work.sweep_bytes(6, len(EDGES), weighted=True) == 8 * 12 + 6 * 8 == 144


def test_unweighted_sweep_bytes_by_hand():
    assert work.sweep_bytes(6, len(EDGES), weighted=False) == 8 * 8 + 6 * 8 == 112


def test_ops_and_least_time():
    assert work.sweep_ops(len(EDGES), True) == 16
    assert work.sweep_ops(len(EDGES), False) == 8
    peaks = {"hbm_bytes_per_s": 144.0, "flops_per_s": 1e9}
    assert work.min_seconds(6, len(EDGES), True, peaks, chips=1) == 1.0
    assert work.min_seconds(6, len(EDGES), True, peaks, chips=4) == 0.25
    # bound by operations where they take longer than the bytes
    slow = {"hbm_bytes_per_s": 1e12, "flops_per_s": 8.0}
    assert work.min_seconds(6, len(EDGES), False, slow, chips=1) == 1.0
