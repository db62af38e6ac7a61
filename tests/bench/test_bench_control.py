"""The lower-precision controls come out not correct at a test size: the
program's own narrower wire type for the exchanged values, one step below
what each configuration states (float32 PageRank -> bfloat16 payloads;
int32 WCC labels -> int16, at a scale whose vertex ids int16 cannot hold).
bench/control.py runs the same controls on the chip at the cells' size."""
import pytest

from _bench import run_small, small_cell
from bench import control


@pytest.mark.parametrize("cell,scale", [("g500-s20-hybrid.pagerank", 10),
                                        ("g500-s20-vertical.wcc", 16)])
def test_control_is_not_correct(cell, scale):
    c = small_cell(cell, scale)
    sound = run_small(c)
    low = run_small(c, engine_kw=control.control_kw(c["traffic"]))
    name = c["traffic"]["check"]["name"]
    assert sound["correct"], sound["checks"]
    assert not low["correct"], low["checks"]
    assert low["checks"][name]["value"] >= 3 * max(sound["checks"][name]["value"],
                                                   c["traffic"]["check"]["limit"])
