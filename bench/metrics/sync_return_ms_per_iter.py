"""Host ms per iteration from the end of the step's run on the device to
the return of ``float(delta)`` (``pmv.sync`` spans): the device-to-host
return of the step's result, where host stalls sit (layer: engine loop,
core/engine.py PMVEngine.run)."""
import bisect


def read(r):
    if r.step is None or not r.iterations:
        return None
    syncs = [e.end for e in r.host if e.name == "pmv.sync" and r.lo <= e.end <= r.hi]
    if not syncs:
        return None
    ends = sorted(e.end for e in r.step)
    total = 0.0
    for t in syncs:
        k = bisect.bisect_right(ends, t) - 1
        if k >= 0:
            total += t - ends[k]
    return total * 1e-6 / r.iterations
