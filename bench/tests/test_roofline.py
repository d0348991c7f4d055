"""The least bytes of a level's support work come from the level's shapes
alone: no tile size enters, and a schedule that groups the candidates
differently moves the program's tile count but not the byte count."""
import inspect
import math

import numpy as np

from bench import roofline
from bench.gen import common, molecule
from bench.ref import miner


def test_closed_form():
    s = roofline.LevelShape(partitions=8, graphs=256, parents=90, triples=45,
                            candidates=2565, embeddings=64, vertices=3,
                            occurrences=28)
    per_graph = 90 * (64 * 3 * 4 + 64) + 45 * 28 * 9
    assert roofline.support_bytes(s) == 8 * (256 * per_graph + 2565 * 8)


def test_no_tiling_parameter():
    for fn in (roofline.support_bytes, roofline.level_shapes):
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
