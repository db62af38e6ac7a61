"""The engine-loop readers (``sync_return_ms_per_iter``,
``stats_ms_per_iter``, ``dispatch_ms_per_iter``) on hand-built host spans,
on the recorded traces that hold none, and on a chip trace that holds them
(``bench/testdata/spans/``, outside the glob of test_bench_trace.py)."""
import glob
import os

import pytest

from _bench import ROOT, run
from bench import trace as tr
from bench.reading import Reading

READERS = ["sync_return_ms_per_iter", "stats_ms_per_iter", "dispatch_ms_per_iter"]
MS = 1e6  # ns

# Three iterations in a window of [0, 100] ms.  The step runs on two devices;
# iteration 1 syncs 3 ms after the later device ends; iteration 2's sync
# returns 1 ms after its step; iteration 3 has no spans at all.
STEP = [tr.Event("jit_step", 2 * MS, 20 * MS, 0), tr.Event("jit_step", 2 * MS, 21 * MS, 1),
        tr.Event("jit_step", 30 * MS, 50 * MS, 0), tr.Event("jit_step", 30 * MS, 50 * MS, 1),
        tr.Event("jit_step", 60 * MS, 80 * MS, 0), tr.Event("jit_step", 60 * MS, 80 * MS, 1)]
HOST = [tr.Event("bench.solve", 0, 100 * MS),
        tr.Event("pmv.iteration", 0.5 * MS, 24 * MS),
        tr.Event("pmv.dispatch", 0.5 * MS, 2.5 * MS),
        tr.Event("pmv.sync", 2.5 * MS, 24 * MS),
        tr.Event("pmv.stats", 24.5 * MS, 27.5 * MS),
        tr.Event("pmv.iteration", 28 * MS, 51 * MS),
        tr.Event("pmv.dispatch", 28 * MS, 29 * MS),
        tr.Event("pmv.sync", 29 * MS, 51 * MS),
        tr.Event("pmv.stats", 51 * MS, 53 * MS),
        # a span of another solve, outside the window
        tr.Event("pmv.stats", 120 * MS, 130 * MS),
        tr.Event("pmv.sync", 110 * MS, 120 * MS)]
EXPECTED = {"sync_return_ms_per_iter": (3 + 1) / 3,
            "stats_ms_per_iter": (3 + 2) / 3,
            "dispatch_ms_per_iter": (2 + 1) / 3}


def _reading(host, step, iterations=3, lo=0, hi=100 * MS):
    return Reading(ops=[], host=host, lo=lo, hi=hi, iterations=iterations, chips=2,
                   n=6, edge_slots=8, weighted=False, peaks={}, prepare_s=1.0,
                   matrix_bytes=80, step=step)


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_hand_built_spans(metric):
    assert run.metric_reader(metric)(_reading(HOST, STEP)) == pytest.approx(
        EXPECTED[metric])


@pytest.mark.parametrize("metric", READERS)
def test_reader_returns_nothing_without_its_spans(metric):
    host = [e for e in HOST if not e.name.startswith("pmv.")]
    assert run.metric_reader(metric)(_reading(host, STEP)) is None
    assert run.metric_reader(metric)(_reading(HOST, STEP, iterations=0)) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_counts_only_the_window(metric):
    """A window that ends at 26 ms cuts the first stats span to 1.5 ms and
    leaves out everything of the second iteration."""
    r = _reading(HOST, STEP, iterations=1, hi=26 * MS)
    want = {"sync_return_ms_per_iter": 3.0, "stats_ms_per_iter": 1.5,
            "dispatch_ms_per_iter": 2.0}
    assert run.metric_reader(metric)(r) == pytest.approx(want[metric])


def test_sync_return_needs_the_step_programs():
    """Without ``XLA Modules`` events there is no step end to measure from;
    a sync that returns before any step ended adds nothing."""
    read = run.metric_reader("sync_return_ms_per_iter")
    assert read(_reading(HOST, None)) is None
    early = [tr.Event("pmv.sync", 0.5 * MS, 1 * MS)]
    assert read(_reading(early, STEP, iterations=1)) == 0.0


OLD = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "*.xplane.pb")))
SPANS = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "spans", "*.xplane.pb")))


def _recorded(path, iterations):
    profile = tr.load(path)
    host = tr.host_events(profile)
    lo, hi = tr.window(host)
    step = [e for e in tr.module_events(profile) if e.name == "jit_step"]
    return Reading(ops=tr.device_ops(profile), host=host, lo=lo, hi=hi,
                   iterations=iterations, chips=1, n=6, edge_slots=8, weighted=False,
                   peaks={}, prepare_s=1.0, matrix_bytes=80, step=step)


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("path", OLD, ids=os.path.basename)
def test_readers_find_nothing_in_traces_without_spans(metric, path):
    r = _recorded(path, iterations=2)
    assert r.step
    assert run.metric_reader(metric)(r) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_the_chip_trace_with_spans(metric):
    assert SPANS, "no recorded trace under bench/testdata/spans"
    for path in SPANS:
        assert os.path.getsize(path) < 1 << 20
        r = _recorded(path, iterations=sum(
            1 for e in tr.host_events(tr.load(path)) if e.name == "pmv.iteration"))
        assert r.iterations > 0 and r.step
        value = run.metric_reader(metric)(r)
        assert value is not None and value > 0, (path, value)
