"""The four-chip cell on 4 forced CPU devices, all in one child process
(``with_devices``): a whole run is correct at a test size, with every
matrix leaf over the 4-device mesh; its vector is bitwise the same layout
emulated on one device; the compiled step exchanges by an all-to-all and
sums delta by an all-reduce; and the run comes out not correct under the
lower-precision control and with the timed path broken underneath."""
import copy

import pytest

from _bench import run, run_small, small_cell, with_devices

CELL = "g500-s20-vertical-4chip.pagerank"
SCALE = 12
GRAPH_SEED = 2**31 + 31


def _summary(result: dict) -> dict:
    return {k: result[k] for k in ("correct", "attempted", "failed", "checks")}


def _readings(scale: int) -> dict:
    """Everything the tests below check, read on this process's devices."""
    import jax
    import numpy as np

    from bench import control, gen, hlo
    from test_bench_faults import FAULTS

    cell = small_cell(CELL, scale)
    out = {"devices": len(jax.devices()), "sound": _summary(run_small(cell)),
           "control": _summary(run_small(
               cell, engine_kw=control.control_kw(cell["traffic"]))),
           "faults": {}}
    for name, plant in sorted(FAULTS.items()):
        with pytest.MonkeyPatch.context() as mp:
            plant(mp)
            out["faults"][name] = _summary(run_small(cell))

    conf, traffic = cell["config"], cell["traffic"]
    edges, n = gen.graph500(scale, conf["edgefactor"], conf["rmat"]["a"],
                            conf["rmat"]["b"], conf["rmat"]["c"], conf["graph_seed"],
                            GRAPH_SEED, conf["layout"]["blocks"])
    spec = run.make_spec(traffic, n)
    eng = run.build_engine(conf, edges, n, jax.devices()[:4])
    matrix = eng.prepare(spec)[1]
    out["leaf_devices"] = sorted({len(x.sharding.device_set)
                                  for x in jax.tree.leaves(matrix)})
    out["opcodes"] = sorted(set().union(*hlo.op_kinds(run.step_hlo(eng, spec)).values()))
    got = eng.run(spec, max_iters=traffic["max_iters"], tol=traffic["tol"]).v
    emulated = copy.deepcopy(conf)
    emulated["layout"]["mesh"] = None
    want = run.build_engine(emulated, edges, n, jax.devices()[:1]).run(
        spec, max_iters=traffic["max_iters"], tol=traffic["tol"]).v
    out["bitwise"] = bool(np.array_equal(got, want))
    out["max_abs_diff"] = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    return out


@pytest.fixture(scope="module")
def readings():
    return with_devices(4, _readings, SCALE)


def test_run_on_the_mesh_is_correct(readings):
    sound = readings["sound"]
    assert readings["devices"] == 4
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert sound["checks"]["config_departures"]["value"] == 0
    err = sound["checks"]["pr_max_rel_err"]
    assert err["value"] <= err["limit"]


def test_every_matrix_leaf_spans_four_devices(readings):
    assert readings["leaf_devices"] == [4]


def test_vector_is_bitwise_the_layout_emulated_on_one_device(readings):
    assert readings["bitwise"], readings["max_abs_diff"]


def test_compiled_step_exchanges_by_all_to_all_and_sums_by_all_reduce(readings):
    assert {"all-to-all", "all-reduce"} <= set(readings["opcodes"])


def test_control_is_not_correct(readings):
    """bfloat16 payloads, one step below the configuration's float32."""
    sound, low = readings["sound"]["checks"], readings["control"]["checks"]
    assert not readings["control"]["correct"], low
    limit = sound["pr_max_rel_err"]["limit"]
    assert low["pr_max_rel_err"]["value"] >= 3 * max(sound["pr_max_rel_err"]["value"],
                                                     limit)


@pytest.mark.parametrize("fault", ["altered_answer", "half_the_edges", "no_exchange",
                                   "unchanged_state"])
def test_broken_timed_path_is_not_correct(readings, fault):
    result = readings["faults"][fault]
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1


def test_run_small_refuses_a_cell_on_more_devices_than_the_process_has():
    import jax

    cell = small_cell(CELL, SCALE)
    cell["workload"]["chips"] = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match="with_devices"):
        run_small(cell)
