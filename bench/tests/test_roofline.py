"""The least bytes of a level's support work come from the level's shapes
alone: no tile size enters, and a schedule that groups the candidates
differently moves the program's tile count but not the byte count.  The
count from the real embeddings sums the reference's occurrence lists and
never passes the dense store's."""
import inspect
import math

import numpy as np
import pytest

from bench import roofline
from bench.gen import common, molecule
from bench.ref import miner


def test_closed_form():
    s = roofline.LevelShape(partitions=8, graphs=256, parents=90, triples=45,
                            candidates=2565, embeddings=64, vertices=3,
                            occurrences=28, rows=7000, ids=20500,
                            edge_rows=9000)
    per_graph = 90 * (64 * 3 * 4 + 64) + 45 * 28 * 9
    assert roofline.support_bytes(s) == 8 * (256 * per_graph + 2565 * 8)
    assert roofline.least_support_bytes(s) == (20500 * 4 + 7000 + 9000 * 9
                                               + 8 * 2565 * 8)


def test_no_tiling_parameter():
    for fn in (roofline.support_bytes, roofline.least_support_bytes,
               roofline.level_shapes):
        names = set(inspect.signature(fn).parameters)
        assert not {n for n in names if "tile" in n or "sched" in n}
    assert not any("tile" in f.name
                   for f in roofline.dataclasses.fields(roofline.LevelShape))


def _shapes(ref, n):
    return roofline.level_shapes(ref, n, 8, 32)


def test_bytes_do_not_depend_on_the_schedule():
    from repro.core.candgen import schedule_candidates

    db = common.reorder(molecule.generate(200, 0), 1)
    ref = miner.mine(db, math.ceil(0.1 * len(db)))
    before = [roofline.support_bytes(s) for s in _shapes(ref, len(db))]
    level = ref.levels[2]
    meta = np.array([[c.parent, c.stub, c.to, int(c.forward), hash(c.triple)
                      % 64] for c in level.candidates], np.int32)
    tiles = {tc: schedule_candidates(meta, tc).n_tiles for tc in (1, 8)}
    assert tiles[1] != tiles[8]
    # regroup the level's candidates (another order, as another
    # schedule would visit them): the count stays
    rng = np.random.default_rng(0)
    level.candidates = [level.candidates[i]
                        for i in rng.permutation(len(level.candidates))]
    after = [roofline.support_bytes(s) for s in _shapes(ref, len(db))]
    assert before == after and all(b > 0 for b in before)


def test_shapes_follow_the_reference_levels():
    db = common.reorder(molecule.generate(200, 0), 1)
    ref = miner.mine(db, math.ceil(0.1 * len(db)))
    shapes = _shapes(ref, len(db))
    assert [s.candidates for s in shapes] == [
        len(lv.candidates) for lv in ref.levels[1:] if lv.candidates]
    assert [s.vertices for s in shapes][:2] == [2, 3]
    for s in shapes:
        assert s.graphs == 25 and s.embeddings >= 32
        assert s.embeddings & (s.embeddings - 1) == 0


def _brute_force(ref):
    """Per level with candidates: the touched parents' embeddings, their
    vertex ids and the touched triples' occurrences, counted one by one
    over the reference's occurrence lists."""
    out = []
    for parent_level, level in zip(ref.levels, ref.levels[1:]):
        if not level.candidates:
            continue
        parents = sorted(parent_level.frequent)
        rows = ids = edge_rows = 0
        for p in {c.parent for c in level.candidates}:
            for embs in parent_level.frequent[parents[p]].values():
                for emb in embs:
                    rows += 1
                    ids += len(emb)
        for t in {c.triple for c in level.candidates}:
            for occs in ref.edge_occ[t].values():
                edge_rows += len(occs)
        out.append((rows, ids, edge_rows))
    return out


def test_real_rows_sum_the_reference_occurrence_lists():
    db = common.reorder(molecule.generate(200, 0, avg_edges=12.0,
                                          n_elabels=1,
                                          extra_edge_prob=0.3), 2)
    ref = miner.mine(db, math.ceil(0.1 * len(db)))
    shapes = _shapes(ref, len(db))
    assert len(shapes) >= 3
    assert [(s.rows, s.ids, s.edge_rows) for s in shapes] == \
        _brute_force(ref)
    # rings: a level whose parents differ in vertex count
    assert any(s.ids < s.rows * s.vertices for s in shapes)


@pytest.mark.parametrize("n_elabels,extra", [(3, 0.16), (1, 0.3)])
def test_least_bytes_never_pass_the_dense_count(n_elabels, extra):
    db = common.reorder(molecule.generate(200, 0, avg_edges=12.0,
                                          n_elabels=n_elabels,
                                          extra_edge_prob=extra), 3)
    ref = miner.mine(db, math.ceil(0.1 * len(db)))
    for s in _shapes(ref, len(db)):
        assert 0 < roofline.least_support_bytes(s) <= \
            roofline.support_bytes(s)
