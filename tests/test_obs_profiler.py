"""repro.obs spans in ``jax.profiler`` traces: while a profiler session is
active every span, with ``obs`` off or on, lands on the trace's host plane,
so the engine loop's host work shows on the device trace's clock."""
import collections
import glob
import os

import jax
import pytest

from repro.core import PMVEngine, pagerank
from repro.graph import erdos_renyi
from repro.obs import NULL_RECORDER, Recorder

N = 300
ITERS = 3
PER_ITERATION = ("pmv.iteration", "pmv.dispatch", "pmv.sync", "pmv.stats")


@pytest.fixture(scope="module")
def graph():
    return erdos_renyi(N, 1800, seed=7)


def _host_events(log_dir):
    """(name, start_ns, end_ns) of every event on the trace's host plane."""
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    plane = jax.profiler.ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [(e.name, e.start_ns, e.end_ns) for line in plane.lines
            for e in line.events]


def _traced_solve(graph, log_dir, obs=None):
    eng = PMVEngine(graph, N, b=4, strategy="vertical", obs=obs)
    spec = pagerank(N)
    eng.run(spec, max_iters=1, tol=0.0)  # prepare and compile outside the trace
    jax.profiler.start_trace(str(log_dir))
    try:
        res = eng.run(spec, max_iters=ITERS, tol=0.0)
    finally:
        jax.profiler.stop_trace()
    assert res.iterations == ITERS
    return [e for e in _host_events(str(log_dir)) if e[0].startswith("pmv.")]


def test_engine_spans_reach_the_profiler_with_obs_off(graph, tmp_path):
    spans = _traced_solve(graph, tmp_path)
    counts = collections.Counter(name for name, _, _ in spans)
    for name in PER_ITERATION:
        assert counts[name] == ITERS, (name, counts)
    assert counts["pmv.setup"] == 1 and counts["pmv.result"] == 1, counts
    assert counts["pmv.checkpoint"] == 0
    iterations = [(s, e) for name, s, e in spans if name == "pmv.iteration"]
    for name, s, e in spans:
        if name in ("pmv.dispatch", "pmv.sync"):
            assert any(lo <= s and e <= hi for lo, hi in iterations), name
    # each iteration's stats follow its sync, outside the iteration span
    syncs = sorted(s for name, s, _ in spans if name == "pmv.sync")
    stats = sorted(s for name, s, _ in spans if name == "pmv.stats")
    assert all(a < b for a, b in zip(syncs, stats))
    assert not any(lo <= s <= hi for s in stats for lo, hi in iterations)


def test_recorder_spans_match_the_profiler_trace(graph, tmp_path):
    rec = Recorder()
    spans = _traced_solve(graph, tmp_path, obs=rec)
    in_trace = collections.Counter(name for name, _, _ in spans)
    recorded = collections.Counter(e["name"] for e in rec.spans("pmv."))
    # the warm-up solve ran outside the session: one setup, iteration and
    # so on more in the recorder
    recorded.subtract({name: 1 for name in PER_ITERATION + ("pmv.setup", "pmv.result")})
    assert in_trace == +recorded
    assert rec.counter("pmv.iterations").value == 1 + ITERS


def test_engine_run_does_not_fence(graph, monkeypatch):
    """``float(delta)`` waits for the step; the loop adds no fence that would
    move the wait, and change the schedule, with a recorder on."""
    def no_fence(self, x):
        raise AssertionError("PMVEngine.run fenced")

    monkeypatch.setattr(Recorder, "fence", no_fence)
    res = PMVEngine(graph, N, b=4, strategy="vertical", obs=Recorder()).run(
        pagerank(N), max_iters=2, tol=0.0)
    assert res.iterations == 2


def test_null_span_is_shared_outside_a_session_and_traced_inside(tmp_path):
    assert NULL_RECORDER.span("a") is NULL_RECORDER.span("b")
    jax.profiler.start_trace(str(tmp_path))
    try:
        sp = NULL_RECORDER.span("obs.null", {"k": 1})
        assert sp is not NULL_RECORDER.span("b")
        with sp as entered:
            entered.set("k", 2)  # dropped: the profiler sees the name alone
        rec = Recorder()
        with rec.span("obs.recorded") as sp:
            sp.set("k", 3)
    finally:
        jax.profiler.stop_trace()
    assert NULL_RECORDER.span("a") is NULL_RECORDER.span("b")
    names = {name for name, _, _ in _host_events(str(tmp_path))}
    assert {"obs.null", "obs.recorded"} <= names
    assert rec.spans("obs.recorded")[0]["attrs"] == {"k": 3}
