"""A whole run, with the harness's look for a chip skipped, comes out correct
on the sound program and not correct with the timed path broken underneath:
a step that returns its state unchanged, half of the edges left out (the
weights then averaged over the rest), the exchange between workers left out,
and one answer altered where it is produced."""
import numpy as np
import pytest

from _bench import ONE_CHIP_CELLS, run_small, small_cell


def _unchanged_state(monkeypatch):
    from repro.core import engine

    make_step = engine.make_step

    def broken(spec, cfg, mesh=None, axis_name="workers"):
        step = make_step(spec, cfg, mesh, axis_name)

        def same_state(matrix, v, *rest):
            return (v,) + tuple(step(matrix, v, *rest)[1:])
        return same_state
    monkeypatch.setattr(engine, "make_step", broken)


def _half_the_edges(monkeypatch):
    from repro.core import engine

    partition_graph = engine.partition_graph
    monkeypatch.setattr(engine, "partition_graph",
                        lambda edges, *a, **k: partition_graph(edges[::2], *a, **k))


def _no_exchange(monkeypatch):
    from repro.core import placement

    monkeypatch.setattr(placement, "_all_to_all", lambda x, axis_name: x)


def _altered_answer(monkeypatch):
    from repro.core.partition import Partition

    from_blocked = Partition.from_blocked

    def altered(self, xb):
        out = np.array(from_blocked(self, xb))
        out[len(out) // 2] = out[len(out) // 2] * 2 + 1
        return out
    monkeypatch.setattr(Partition, "from_blocked", altered)


FAULTS = {"unchanged_state": _unchanged_state, "half_the_edges": _half_the_edges,
          "no_exchange": _no_exchange, "altered_answer": _altered_answer}


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_sound_run_is_correct(cell):
    result = run_small(small_cell(cell))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    for value in result["metrics"].values():
        assert value["value"] >= 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = run_small(small_cell(cell))
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1
