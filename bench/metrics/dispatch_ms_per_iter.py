"""Host ms per iteration of the step's dispatch, from the call until it
returns (``pmv.dispatch`` spans) (layer: engine loop, core/engine.py
PMVEngine.run)."""

SPAN = "pmv.dispatch"


def read(r):
    spans = [min(e.end, r.hi) - max(e.start, r.lo) for e in r.host if e.name == SPAN]
    spans = [d for d in spans if d > 0]
    if not spans or not r.iterations:
        return None
    return sum(spans) * 1e-6 / r.iterations
