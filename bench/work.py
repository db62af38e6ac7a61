"""The least work of one GIM-V sweep, computed from the graph alone, so that
a roofline share reads the same whatever implements the sweep.

Per directed edge slot: the 4 B source id, the 4 B source value gathered
through it, and a 4 B weight where the algorithm's matrix has per-edge values
(PageRank: 1/out-degree; WCC: none).  Per vertex: one 4 B read and one 4 B
write of the vector.  Operations: one combine2 per weighted edge and one
combineAll per edge.
"""
from __future__ import annotations

ID_BYTES = 4
VALUE_BYTES = 4
WEIGHT_BYTES = 4


def sweep_bytes(n: int, edge_slots: int, weighted: bool) -> int:
    per_edge = ID_BYTES + VALUE_BYTES + (WEIGHT_BYTES if weighted else 0)
    return edge_slots * per_edge + n * 2 * VALUE_BYTES


def sweep_ops(edge_slots: int, weighted: bool) -> int:
    return edge_slots * (2 if weighted else 1)


def min_seconds(n: int, edge_slots: int, weighted: bool, peaks: dict,
                chips: int) -> float:
    """The least time ``chips`` chips with these peaks could take for one
    sweep: the larger of bytes over bandwidth and operations over FLOP/s."""
    return max(sweep_bytes(n, edge_slots, weighted) / (chips * peaks["hbm_bytes_per_s"]),
               sweep_ops(edge_slots, weighted) / (chips * peaks["flops_per_s"]))
