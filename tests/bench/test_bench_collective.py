"""The collectives' reader (``collective_ms_per_iter``) on synthetic events
and on a four-chip chip trace (``bench/testdata/four_chip/``, outside the
glob of test_bench_trace.py), and the other device readers' values on the
recorded one-chip traces, pinned."""
import glob
import json
import os

import pytest

from _bench import ROOT, run
from bench import trace as tr
from bench.reading import Reading

READ = run.metric_reader("collective_ms_per_iter")
COLLECTIVE_OPCODES = {op + suffix for op in ("all-to-all", "all-reduce", "all-gather",
                                             "collective-permute", "reduce-scatter")
                      for suffix in ("", "-start", "-done")}
MS = 1e6  # ns


def _reading(ops, hlo=None, step=None, iterations=2, chips=2):
    return Reading(ops=ops, host=[], lo=0, hi=100 * MS, iterations=iterations,
                   chips=chips, n=6, edge_slots=8, weighted=True, peaks={},
                   prepare_s=1.0, matrix_bytes=80, hlo=hlo or {}, step=step)


def test_sync_async_and_fused_collectives_count_on_every_chip():
    """Two iterations on two chips.  Chip 0: a sync all-to-all named with
    '_' (3 ms), an async all-reduce pair by name alone (1 + 2 ms), a fusion
    that runs an all-gather (4 ms) and a gather that is no collective.  Chip
    1: the all-to-all (5 ms) and a collective-permute's async pair whose
    kinds, not names, say so (1 + 1 ms)."""
    ops = [tr.Event("all_to_all.4", 0, 3 * MS, 0),
           tr.Event("all-reduce-start.1", 10 * MS, 11 * MS, 0),
           tr.Event("all-reduce-done.1", 20 * MS, 22 * MS, 0),
           tr.Event("fusion.7", 30 * MS, 34 * MS, 0),
           tr.Event("fusion.8", 40 * MS, 90 * MS, 0),
           tr.Event("all_to_all.4", 0, 5 * MS, 1),
           tr.Event("start.2", 10 * MS, 11 * MS, 1),
           tr.Event("done.2", 12 * MS, 13 * MS, 1)]
    hlo = {"all_to_all.4": frozenset({"all-to-all"}),
           "fusion.7": frozenset({"fusion", "all-gather", "add"}),
           "fusion.8": frozenset({"fusion", "gather"}),
           "start.2": frozenset({"collective-permute-start"}),
           "done.2": frozenset({"collective-permute-done"})}
    chip0, chip1 = 3 + 1 + 2 + 4, 5 + 1 + 1
    assert READ(_reading(ops, hlo)) == pytest.approx((chip0 + chip1) / 2 / 2)


def test_ops_outside_the_steps_runs_do_not_count():
    """The same instruction name in another program is not the step's."""
    ops = [tr.Event("all-reduce", 0, 2 * MS, 0), tr.Event("all-reduce", 60 * MS, 90 * MS, 0)]
    step = [tr.Event("jit_body", 0, 50 * MS, 0)]
    r = _reading(ops, {"all-reduce": frozenset({"all-reduce"})}, step, iterations=1, chips=1)
    assert READ(r) == pytest.approx(2.0)


def test_nothing_to_read_without_a_collective():
    ops = [tr.Event("fusion.8", 0, 50 * MS, 0), tr.Event("ell_gimv.3", 50 * MS, 60 * MS, 0)]
    hlo = {"fusion.8": frozenset({"fusion", "gather"})}
    assert READ(_reading(ops, hlo)) is None
    assert READ(_reading([])) is None


FOUR_CHIP = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "four_chip",
                                          "*.xplane.pb")))


def _recorded(path):
    profile = tr.load(path)
    host = tr.host_events(profile)
    lo, hi = tr.window(host)
    with open(path.replace(".xplane.pb", ".hlo_kinds.json")) as f:
        kinds = {k: frozenset(v) for k, v in json.load(f).items()}
    return Reading(ops=tr.device_ops(profile), host=host, lo=lo, hi=hi,
                   iterations=sum(1 for e in host if e.name == "pmv.iteration"),
                   chips=4, n=6, edge_slots=8, weighted=True, peaks={},
                   prepare_s=1.0, matrix_bytes=80, hlo=kinds,
                   step=tr.module_events(profile))


def test_reader_reads_the_four_chip_trace():
    """A scale-13 solve of the four-chip cell, traced on a 2x2 v5e: the step
    runs an all-to-all and an all-reduce on every chip, and the reader reads
    exactly the device time of the instructions whose opcodes are
    collectives."""
    assert FOUR_CHIP, "no recorded trace under bench/testdata/four_chip"
    for path in FOUR_CHIP:
        assert os.path.getsize(path) < 1 << 20
        r = _recorded(path)
        assert r.iterations > 0 and r.devices() == [0, 1, 2, 3]
        collective = {name for name, ops in r.hlo.items() if ops & COLLECTIVE_OPCODES}
        assert {"all-to-all", "all-reduce"} <= set().union(*(r.hlo[k] for k in collective))
        ran = [e for e in tr.inside(r.ops, r.step) if e.name in collective]
        assert {e.name for e in ran} == collective
        assert {e.device for e in ran} == {0, 1, 2, 3}
        want = sum(tr.op_seconds(ran, r.lo, r.hi).values()) * 1e3 / 4 / r.iterations
        assert READ(r) == pytest.approx(want) and want > 0


# What the device readers read on the recorded one-chip traces (two
# iterations each) with the op kinds stored beside them, and what the
# engine-loop readers read on the trace with spans: pinned, so that a change
# to hlo.py, reading.py or trace.py that moves any of them shows.
PINNED = {
    "g500-s20-hybrid.s12.xplane.pb": {
        "ell_ms_per_iter": 0.055488, "gather_ms_per_iter": 1.981205,
        "scatter_ms_per_iter": 0.811623, "collective_ms_per_iter": None},
    "g500-s20-vertical.s12.xplane.pb": {
        "ell_ms_per_iter": 0.003904, "gather_ms_per_iter": 1.857379,
        "scatter_ms_per_iter": 0.4868375, "collective_ms_per_iter": None},
    "spans/g500-s20-hybrid.s13.xplane.pb": {
        "ell_ms_per_iter": 0.079912, "sync_return_ms_per_iter": 1.782836,
        "stats_ms_per_iter": 3.93417, "dispatch_ms_per_iter": 0.888999},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_readers_read_the_recorded_traces_as_before(name):
    path = os.path.join(ROOT, "bench", "testdata", name)
    profile = tr.load(path)
    host = tr.host_events(profile)
    lo, hi = tr.window(host)
    kinds_path = path.replace(".xplane.pb", ".hlo_kinds.json")
    kinds = {}
    if os.path.exists(kinds_path):
        with open(kinds_path) as f:
            kinds = {k: frozenset(v) for k, v in json.load(f).items()}
    r = Reading(ops=tr.device_ops(profile), host=host, lo=lo, hi=hi,
                iterations=sum(1 for e in host if e.name == "pmv.iteration") or 2,
                chips=1, n=6, edge_slots=8, weighted=True, peaks={}, prepare_s=1.0,
                matrix_bytes=80, hlo=kinds,
                step=[e for e in tr.module_events(profile) if e.name == "jit_step"])
    for metric, want in PINNED[name].items():
        got = run.metric_reader(metric)(r)
        assert got == (want if want is None else pytest.approx(want, rel=1e-9)), metric
