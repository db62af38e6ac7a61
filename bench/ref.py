"""Plain references (NumPy / SciPy, float64) and the comparisons that decide
``correct``.  Nothing here imports the program under test.

PageRank follows the engine's stated semantics: x' = (1 - d)/n + d * A x with
A[i, j] = 1 / out(j) for every edge j -> i, so the rank of vertices without
out-edges leaks (Graphalytics would spread it; see PERF.md).  WCC labels each
vertex with the smallest vertex id of its weakly connected component.
"""
from __future__ import annotations

import numpy as np


def transition(edges: np.ndarray, n: int):
    """A[i, j] = 1 / out(j) for every edge j -> i (column-stochastic)."""
    import scipy.sparse as sp

    src, dst = edges[:, 0], edges[:, 1]
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    vals = 1.0 / np.maximum(out_deg[src], 1.0)
    return sp.csr_matrix((vals, (dst, src)), shape=(n, n))


def pagerank(edges: np.ndarray, n: int, iters: int, damping: float = 0.85) -> np.ndarray:
    """``iters`` power iterations from the uniform vector."""
    a = transition(edges, n)
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        x = (1.0 - damping) / n + damping * (a @ x)
    return x


def wcc(edges: np.ndarray, n: int) -> np.ndarray:
    """Component labels, each the minimum vertex id of its component."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    g = sp.csr_matrix((np.ones(len(edges), np.int8), (edges[:, 0], edges[:, 1])),
                      shape=(n, n))
    _, comp = connected_components(g, directed=True, connection="weak")
    first = np.full(comp.max() + 1, n, np.int64)
    np.minimum.at(first, comp, np.arange(n))
    return first[comp]


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest per-vertex |got - want| / |want| (PageRank values are all at
    least (1 - d)/n, so no vertex divides by zero)."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def mismatched(got: np.ndarray, want: np.ndarray) -> int:
    """Vertices whose label differs from the reference's."""
    return int(np.count_nonzero(np.asarray(got).astype(np.int64) != want))


# algorithm -> (reference(edges, n, traffic), compare(got, want), name of the
# number compared).  An algorithm's limit lives in its traffic file.
def reference(traffic: dict, edges: np.ndarray, n: int) -> np.ndarray:
    alg = traffic["algorithm"]
    if alg == "pagerank":
        return pagerank(edges, n, traffic["max_iters"], traffic["damping"])
    if alg == "wcc":
        return wcc(edges, n)
    raise ValueError(f"no reference for algorithm {alg!r}")


def compare(traffic: dict, got: np.ndarray, want: np.ndarray) -> float | int:
    alg = traffic["algorithm"]
    if alg == "pagerank":
        return max_rel_err(got, want)
    if alg == "wcc":
        return mismatched(got, want)
    raise ValueError(f"no comparison for algorithm {alg!r}")
