"""The device Graph500 generator (bench/gen.py) on the CPU."""
import numpy as np
import pytest

from _bench import CELLS, CHIPS, small_cell, with_devices
from bench import gen

ABC = (0.57, 0.19, 0.19)


GRAPH_SEED = 20221


def _graph(seed, scale=10, graph_seed=GRAPH_SEED, modulus=16):
    return gen.graph500(scale, 16, *ABC, graph_seed, seed, modulus)


def test_deterministic_per_seed():
    a, n = _graph(7)
    b, _ = _graph(7)
    assert n == 1024 and a.dtype == np.int32 and a.shape[1] == 2
    assert np.array_equal(a, b)


def test_different_seed_gives_different_graph():
    a, _ = _graph(7)
    b, _ = _graph(8)
    assert not (a.shape == b.shape and np.array_equal(a, b))


def test_seeds_over_32_bits_are_distinct():
    a, _ = _graph(7)
    b, _ = _graph(2**32 + 7)
    c, _ = _graph(2**31 + 7)
    assert len({a.tobytes(), b.tobytes(), c.tobytes()}) == 3


def test_symmetric_no_self_loops_no_duplicates():
    e, n = _graph(2**31 + 3)
    assert e.min() >= 0 and e.max() < n
    assert np.all(e[:, 0] != e[:, 1])
    key = e[:, 0].astype(np.int64) * n + e[:, 1]
    assert len(np.unique(key)) == len(key)
    rev = e[:, 1].astype(np.int64) * n + e[:, 0]
    assert np.array_equal(np.sort(key), np.sort(rev))


def test_seeds_relabel_within_residue_classes():
    """Two seeds give the same graph up to a relabelling that moves each
    residue class mod 16 onto another and keeps every vertex's quotient by
    16 (its local id in its block): the same number of edges between the
    same pairs of local ids, in block pairs that changed places."""
    a, n = _graph(11)
    b, _ = _graph(12)
    assert len(a) == len(b)
    pa = np.bincount((a[:, 0] % 16) * 16 + a[:, 1] % 16, minlength=256).reshape(16, 16)
    pb = np.bincount((b[:, 0] % 16) * 16 + b[:, 1] % 16, minlength=256).reshape(16, 16)
    assert sorted(pa.ravel()) == sorted(pb.ravel()) and not np.array_equal(pa, pb)
    local = [np.sort((e[:, 0] // 16).astype(np.int64) * (n // 16) + e[:, 1] // 16)
             for e in (a, b)]
    assert np.array_equal(local[0], local[1])
    assert sorted(np.bincount(a[:, 0] // 16, minlength=n // 16)) == \
        sorted(np.bincount(b[:, 0] // 16, minlength=n // 16))


def test_relabel_is_a_residue_preserving_permutation():
    """The relabelling maps each residue class mod 4 onto one class, whole,
    and keeps every id's quotient by 4."""
    p = np.asarray(gen._relabel(gen.seed_key(3), n=64, modulus=4))
    assert sorted(p.tolist()) == list(range(64))
    assert np.array_equal(p // 4, np.arange(64) // 4)
    tau = p[:4] % 4
    assert sorted(tau.tolist()) == [0, 1, 2, 3]
    assert np.array_equal(p % 4, tau[np.arange(64) % 4])


def test_edge_slots_match_the_host_generator_within_one_percent():
    from repro.graph import rmat
    from repro.graph.generators import symmetrize_edges

    scale, seed = 14, 5
    e, n = _graph(0, scale, graph_seed=seed)
    perm = np.random.default_rng(seed).permutation(n)
    host = symmetrize_edges(perm[rmat(scale, 16 << scale, seed=seed)])
    assert abs(len(e) / len(host) - 1) < 0.01


def _step_texts(cell: str) -> list[str]:
    """The step program of two seeds, lowered over the cell's own devices."""
    import re

    import jax

    from bench import run

    c = small_cell(cell)
    conf = c["config"]
    texts = []
    for seed in (2**31 + 21, 2**31 + 22):
        edges, n = gen.graph500(conf["scale"], conf["edgefactor"], *ABC,
                                conf["graph_seed"], seed, conf["layout"]["blocks"])
        spec = run.make_spec(c["traffic"], n)
        eng = run.build_engine(conf, edges, n, jax.devices()[:CHIPS[cell]])
        step, matrix, v0, ctx, mask, _ = eng.prepare(spec)
        text = step.lower(matrix, v0, ctx, mask).as_text()
        texts.append(re.sub(r"loc\(.*?\)", "", text))
    return texts


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_compiles_the_same_step(cell):
    """Two seeds build the same step program, constants and all, so a run
    with a new seed finds it in the compile cache.  A cell on more chips
    than this process sees builds its mesh in a child with that many
    devices."""
    texts = with_devices(CHIPS[cell], _step_texts, cell)
    assert f"mhlo.num_partitions = {CHIPS[cell]} " in texts[0]
    assert texts[0] == texts[1]
