"""The trace reduction (bench/trace.py) on synthetic events, and the op-name
patterns of the per-layer metrics on a trace recorded on the chip."""
import glob
import os

import pytest

from _bench import ROOT, run
from bench import trace as tr
from bench.reading import Reading

# device 0: [0,10] and [5,20] overlap, [30,40] alone, [38,45] overlaps it,
# [90,120] runs past the window's end at 100
OPS = [tr.Event("a", 0, 10, 0), tr.Event("b", 5, 20, 0), tr.Event("c", 30, 40, 0),
       tr.Event("a", 38, 45, 0), tr.Event("d", 90, 120, 0)]


def test_busy_union_of_overlapping_events():
    spans = [(e.start, e.end) for e in OPS]
    assert tr.merged(spans, 0, 100) == [(0, 20), (30, 45), (90, 100)]
    assert tr.busy_ns(spans, 0, 100) == 20 + 15 + 10
    assert tr.idle_share(spans, 0, 100) == pytest.approx(0.55)
    # a window that starts inside an event clips it
    assert tr.busy_ns(spans, 8, 35) == 12 + 5


def test_gaps_and_their_host_labels():
    spans = [(e.start, e.end) for e in OPS]
    assert tr.gaps(spans, 0, 100) == [(20, 30), (45, 90)]
    host = [tr.Event("bench.solve", 0, 100), tr.Event("wait", 40, 95)]
    assert tr.host_label(host, 45, 90) == "wait"
    assert tr.host_label(host, 20, 30) == "bench.solve"
    assert tr.host_label(host, -5, 30) == ""
    bd = tr.breakdown(OPS, host, 0, 100, top=2)
    assert [g[0] for g in bd["idle_gaps"]] == ["wait", "bench.solve"]
    assert bd["idle_gaps"][0][1] == pytest.approx(45e-9)
    assert bd["device_ops"][0] == ["a", pytest.approx(17e-9)]


def _reading(ops, iterations=2, chips=1):
    return Reading(ops=ops, host=[], lo=0, hi=100, iterations=iterations, chips=chips,
                   n=6, edge_slots=8, weighted=True,
                   peaks={"hbm_bytes_per_s": 144.0, "flops_per_s": 1e12},
                   prepare_s=1.5, matrix_bytes=80)


def test_readers_on_synthetic_events():
    r = _reading(OPS + [tr.Event("a", 0, 50, 1)], chips=2)
    assert r.busy_s() == pytest.approx((45 + 50) / 2 * 1e-9)
    assert run.metric_reader("device_idle_share")(r) == pytest.approx(52.5)
    assert r.ms_per_iter(["^a$"]) == pytest.approx((17 + 50) / 2 / 2 * 1e-6)
    assert r.ms_per_iter(["^a$"], device=1) == pytest.approx(50 / 2 * 1e-6)
    assert r.ms_per_iter(["nothing"]) is None
    assert run.metric_reader("matrix_bytes_per_edge")(r) == 10.0
    assert run.metric_reader("prepare_s")(r) == 1.5
    # 144 B at 144 B/s on each of 2 chips: 0.5 s, against 47.5 ns of busy per iteration
    roof = run.metric_reader("gimv_roofline")(r)
    assert roof == pytest.approx(100 * 0.5 / (r.busy_s() / 2))


def test_readers_return_nothing_without_device_ops():
    r = _reading([])
    for name in ("device_idle_share", "gimv_roofline", "ell_ms_per_iter",
                 "gather_ms_per_iter", "scatter_ms_per_iter"):
        assert run.metric_reader(name)(r) is None, name


RECORDED = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_is_small_and_has_device_ops(path):
    assert os.path.getsize(path) < 1 << 20
    ops = tr.device_ops(tr.load(path))
    assert ops and all(e.end >= e.start for e in ops)
    assert tr.window(tr.host_events(tr.load(path))) is not None


@pytest.mark.parametrize("metric", ["ell_ms_per_iter", "gather_ms_per_iter",
                                    "scatter_ms_per_iter"])
def test_op_name_patterns_match_the_recorded_trace(metric):
    """Each reader finds its ops in a trace recorded on the chip, with the
    op kinds of the same step compiled for a described v5e
    (``<trace>.hlo_kinds.json``, what ``run.step_op_kinds`` reads on the chip)."""
    import json

    assert RECORDED, "no recorded trace under bench/testdata"
    hits = 0
    for path in RECORDED:
        profile = tr.load(path)
        lo, hi = tr.window(tr.host_events(profile))
        r = _reading(tr.device_ops(profile), iterations=2)
        r.lo, r.hi = lo, hi
        with open(path.replace(".xplane.pb", ".hlo_kinds.json")) as f:
            r.hlo = {k: frozenset(v) for k, v in json.load(f).items()}
        hits += run.metric_reader(metric)(r) is not None
    assert hits == len(RECORDED), f"{metric} reads nothing in some of {RECORDED}"


def test_hlo_op_kinds_of_the_compiled_step():
    """The step's compiled HLO names the instructions that gather and scatter,
    fused or not, and the per-layer readers find them by those names."""
    import jax

    from _bench import small_cell
    from bench import gen, hlo

    cell = small_cell("g500-s20-vertical.wcc")
    edges, n = gen.graph500(10, 16, 0.57, 0.19, 0.19, 20221, 4, 4)
    spec = run.make_spec(cell["traffic"], n)
    eng = run.build_engine(cell["config"], edges, n, jax.devices()[:1])
    kinds, module = run.step_op_kinds(eng, spec)
    assert module == "jit_step"
    gathers = [k for k, ops in kinds.items() if "gather" in ops]
    scatters = [k for k, ops in kinds.items() if "scatter" in ops]
    assert gathers and scatters
    events = [tr.Event(gathers[0], 0, 10, 0), tr.Event(scatters[0], 20, 50, 0)]
    r = _reading(events, iterations=1)
    r.hlo = kinds
    assert run.metric_reader("gather_ms_per_iter")(r) >= 10e-6
    assert run.metric_reader("scatter_ms_per_iter")(r) >= 30e-6
    assert hlo.op_kinds("ENTRY %main (p: f32[2]) -> f32[2] {\n"
                        "  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%f.1\n"
                        "}\n%f.1 (q: f32[2]) -> f32[2] {\n"
                        "  ROOT %gather.3 = f32[2]{0} gather(%q, %q), slice_sizes={1}\n"
                        "}\n") == {"fusion.1": frozenset({"fusion", "gather"}),
                                   "gather.3": frozenset({"gather"})}


def test_ops_count_only_inside_the_step_program():
    """Instruction names repeat from program to program: a ``fusion.1`` of
    another program is not the step's gather, though the step's HLO says its
    own ``fusion.1`` gathers."""
    ops = [tr.Event("fusion.1", 0, 10, 0), tr.Event("fusion.1", 60, 90, 0),
           tr.Event("fusion.1", 5, 25, 1)]
    r = _reading(ops, iterations=1, chips=2)
    r.hlo = {"fusion.1": frozenset({"fusion", "gather"})}
    assert r.ms_per_iter(opcodes=["gather"], device=0) == pytest.approx(40e-6)
    r.step = [tr.Event("jit_step", 0, 50, 0), tr.Event("jit_step", 0, 50, 1)]
    assert r.ms_per_iter(opcodes=["gather"], device=0) == pytest.approx(10e-6)
    assert run.metric_reader("gather_ms_per_iter")(r) == pytest.approx(15e-6)
    assert tr.inside(ops, [tr.Event("jit_other", 55, 100, 0)]) == [ops[1]]


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_names_its_programs(path):
    """The chip's trace spans each program's ops by an ``XLA Modules`` event
    named as the compiled module; every op of the recorded window runs inside
    one of the step's."""
    profile = tr.load(path)
    lo, hi = tr.window(tr.host_events(profile))
    mods = tr.module_events(profile)
    assert mods and {m.name for m in mods} == {"jit_step"}
    ops = [e for e in tr.device_ops(profile) if lo <= e.start < hi]
    assert ops and tr.inside(ops, mods) == ops
