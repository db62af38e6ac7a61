"""Host ms per iteration spent turning the step's stats into the
per-iteration record (``pmv.stats`` spans: one device-to-host transfer per
stats scalar) (layer: engine loop, core/engine.py PMVEngine.run)."""

SPAN = "pmv.stats"


def read(r):
    spans = [min(e.end, r.hi) - max(e.start, r.lo) for e in r.host if e.name == SPAN]
    spans = [d for d in spans if d > 0]
    if not spans or not r.iterations:
        return None
    return sum(spans) * 1e-6 / r.iterations
