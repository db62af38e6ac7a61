"""The whole sweep's share of its roofline: the least time of one GIM-V
sweep on these chips (work.py: minimal bytes over HBM bandwidth, or
operations over peak FLOP/s, whichever is larger) over the device busy time
per iteration (layer: step)."""
from bench import work


def read(r):
    busy = r.busy_s()
    if busy <= 0 or not r.iterations:
        return None
    least = work.min_seconds(r.n, r.edge_slots, r.weighted, r.peaks, r.chips)
    return 100.0 * least / (busy / r.iterations)
