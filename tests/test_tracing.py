"""The spans, counters and named scopes of a fit (``runtime/tracing.py``).

A small single_sync fit is mined under ``jax.profiler``, and its
``mirage:`` host events are read back from the trace with
``ProfileData``: every phase is there, nested where it runs, with the
args the fit's ``LevelStats`` hold.  The level program's named scopes are
read from its compiled HLO, which must not change otherwise.
"""
import contextlib
import gc
import re
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import level_step, mining
from repro.core.graphdb import random_db
from repro.core.mapreduce import MiningMesh, _materialize_program
from repro.core.mining import Mirage, MirageConfig
from repro.runtime import faults, tracing

SCOPES = ("support_kernel", "reduce", "compact", "audit", "materialize",
          "wire_pack")
# phases that run inside a level of the mining loop
LEVEL_PHASES = {"candgen", "candidate_meta", "schedule", "dispatch",
                "candgen_spec", "wire_wait", "wire_decode",
                "retry_materialize", "audit", "checkpoint"}
# phases that run once, before the first level
PREP_PHASES = {"partition", "edge_ol_build", "level1", "upload"}


def _db():
    return random_db(14, n_vertices=6, extra_edge_prob=0.35, n_vlabels=2,
                     n_elabels=2, seed=11)


def _miner(ckpt_dir, **kw):
    # M starts at 2 so the first levels escalate and retry; the
    # checkpoint directory adds the checkpoint phase; a wide window lets
    # the speculation gate take every level whatever the host's speed
    return Mirage(MirageConfig(minsup=4, n_partitions=2, max_size=4,
                               max_embeddings=2, checkpoint_dir=ckpt_dir,
                               overlap_spec_window=60.0, **kw))


def _traced(fn):
    """``fn()`` under the profiler: (its result, the ``mirage:`` events as
    (phase, start_ns, end_ns, args), by start)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path = next(Path(tdir).glob("plugins/profile/*/*.xplane.pb"))
        pd = ProfileData.from_file(str(path))
    events = [(e.name[len(tracing.PREFIX):], int(e.start_ns),
               int(e.start_ns + e.duration_ns), dict(e.stats))
              for plane in pd.planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith(tracing.PREFIX)]
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.fixture(scope="module")
def warm_fit(tmp_path_factory):
    """A warm fit recorded by the profiler, with one generation-2 GC
    inside its first level."""
    graphs = _db()
    miner = _miner(str(tmp_path_factory.mktemp("ckpt")))
    miner.fit(graphs)
    orig = mining.candidate_meta
    calls = []

    def collecting(*a, **kw):
        if not calls:
            gc.collect()
        calls.append(1)
        return orig(*a, **kw)

    mining.candidate_meta = collecting
    try:
        res, events = _traced(lambda: miner.fit(graphs))
    finally:
        mining.candidate_meta = orig
    return res, events


def test_every_phase_is_a_span_nested_where_it_runs(warm_fit):
    res, events = warm_fit
    phases = {e[0] for e in events}
    assert LEVEL_PHASES | PREP_PHASES | {"fit", "level", "gc"} <= phases
    fit = [e for e in events if e[0] == "fit"]
    assert len(fit) == 1
    levels = [e for e in events if e[0] == "level"]
    assert len(levels) == len(res.stats)
    for ev in events:
        if ev[0] != "fit":
            assert _inside(ev, fit[0]), ev
        if ev[0] in LEVEL_PHASES:
            assert any(_inside(ev, lv) for lv in levels), ev
        if ev[0] in PREP_PHASES:
            assert not any(_inside(ev, lv) for lv in levels), ev
    # the forced collection lies inside the first level
    assert any(_inside(e, levels[0]) for e in events if e[0] == "gc")


def test_wait_and_speculation_nest_in_their_own_level(warm_fit):
    _, events = warm_fit
    levels = [e for e in events if e[0] == "level"]
    for phase in ("wire_wait", "wire_decode", "candgen_spec", "dispatch"):
        evs = [e for e in events if e[0] == phase]
        assert len(evs) == len(levels), phase
        for ev, lv in zip(evs, levels):
            assert _inside(ev, lv), (phase, ev, lv)


def test_level_args_equal_level_stats(warm_fit):
    res, events = warm_fit
    levels = [e[3] for e in events if e[0] == "level"]
    assert [(a["level"], a["candidates"], a["S"], bool(a["retried"]),
             a["escalations"]) for a in levels] == [
        (s.level, s.n_candidates, s.survivor_cap, s.retried, s.escalations)
        for s in res.stats]
    assert any(s.retried for s in res.stats)
    assert {a["spec"] for a in levels} == {"taken"}
    retries = [e[3] for e in events if e[0] == "retry_materialize"]
    assert sum(a["escalations"] for a in retries) > 0


def test_level_stats_read_the_spans_clock(warm_fit):
    """``map_seconds`` runs from the schedule span's start to the
    wire_decode span's end and ``candgen_seconds`` is the candgen_spec
    span: the trace's events and the stats agree to the microseconds the
    annotation takes to open."""
    res, events = warm_fit
    sched = [e for e in events if e[0] == "schedule"]
    dec = [e for e in events if e[0] == "wire_decode"]
    spec = [e for e in events if e[0] == "candgen_spec"]
    for st, s, d, c in zip(res.stats, sched, dec, spec):
        assert st.map_seconds == pytest.approx((d[2] - s[1]) / 1e9,
                                               abs=1e-3)
        assert st.candgen_seconds == pytest.approx((c[2] - c[1]) / 1e9,
                                                   abs=1e-3)


def test_fit_span_counts_gc_and_wire_fetches(warm_fit):
    res, events = warm_fit
    args = next(e[3] for e in events if e[0] == "fit")
    assert args["levels"] == len(res.levels)
    assert args["gc_gen2"] >= 1 and args["gc_s"] > 0
    assert args["wire_fetches"] == len(res.stats)
    gcs = [e for e in events if e[0] == "gc"]
    assert len(gcs) == args["gc_gen2"]
    assert all("collected" in e[3] for e in gcs)


@pytest.fixture(scope="module")
def fresh_fit(tmp_path_factory):
    """A warm fit that generates every level's candidates at the loop
    head (no speculation), recorded by the profiler."""
    graphs = _db()
    miner = _miner(str(tmp_path_factory.mktemp("ckpt")),
                   overlap_candgen=False)
    miner.fit(graphs)
    return _traced(lambda: miner.fit(graphs))


@pytest.mark.parametrize("fit", ["warm_fit", "fresh_fit"])
def test_candgen_spans_count_the_canonicality_walk(fit, request):
    """``tested`` and ``early`` on every candgen span: a fresh generation
    tests at least the candidates it keeps, a narrowed one tests none,
    and the fit's counters are the sum over the spans."""
    res, events = request.getfixturevalue(fit)
    gen = [e[3] for e in events if e[0] == "candgen"]
    spec = [e[3] for e in events if e[0] == "candgen_spec"]
    assert len(gen) == len(res.stats)
    for a in gen + spec:
        assert 0 <= a["early"] <= a["tested"]
    if fit == "warm_fit":
        # the first level generates; the others narrow the speculation
        assert gen[0]["tested"] >= gen[0]["candidates"] > 0
        assert all(a["tested"] == 0 for a in gen[1:])
        assert len(spec) == len(res.stats)
        assert all(a["tested"] > 0 for a in spec)
    else:
        assert spec == []
        assert all(a["tested"] >= a["candidates"] for a in gen)
        assert all(a["tested"] > 0 for a in gen)
    total = next(e[3] for e in events if e[0] == "fit")
    assert total["canon_tested"] == sum(a["tested"] for a in gen + spec)
    assert total["canon_early"] == sum(a["early"] for a in gen + spec)
    assert total["canon_early"] > 0


def test_compiles_counted_on_a_cold_fit_and_none_on_a_warm_one(tmp_path):
    graphs = _db()
    # a rebalance threshold no other test uses keys a fresh level
    # program, so the first fit lowers it
    miner = _miner(str(tmp_path), rebalance_threshold=1.0625)
    _, cold = _traced(lambda: miner.fit(graphs))
    _, warm = _traced(lambda: miner.fit(graphs))

    def compiles(events, phase):
        return [e[3]["compiles"] for e in events if e[0] == phase]

    assert compiles(cold, "fit")[0] > 0
    assert compiles(cold, "dispatch")[0] > 0
    assert sum(compiles(cold, "level")) <= compiles(cold, "fit")[0]
    assert compiles(warm, "fit") == [0]
    assert set(compiles(warm, "level")) == {0}


def test_wire_refetches_count_as_attempts(tmp_path):
    graphs = _db()
    miner = _miner(str(tmp_path))
    miner.fit(graphs)
    with faults.active(faults.FaultSchedule.parse("wire_bitflip@3:bit=19")):
        res, events = _traced(lambda: miner.fit(graphs))
    attempts = [e[3]["attempts"] for e in events if e[0] == "wire_decode"]
    levels = [e[3]["level"] for e in events if e[0] == "level"]
    assert dict(zip(levels, attempts)) == {2: 1, 3: 2, 4: 1}
    fit = next(e[3] for e in events if e[0] == "fit")
    assert fit["wire_fetches"] == len(res.stats) + 1


def test_permute_is_a_span():
    mesh = MiningMesh.single_device()
    arrays = [jax.numpy.zeros((2, 3), jax.numpy.int32) for _ in range(5)]
    out, events = _traced(lambda: level_step.permute_stores(
        mesh, np.array([1, 0], np.int32), *arrays))
    assert [e[0] for e in events if e[0] != "gc"] == ["permute"]
    assert len(out) == 5


def test_span_reports_counter_changes_and_its_length():
    def counted():
        with tracing.Span("test", counts={"n": "wire_fetches"}) as sp:
            tracing.count("wire_fetches", 2)
            assert sp.elapsed() >= 0
        return sp

    sp, events = _traced(counted)
    events = [e for e in events if e[0] != "gc"]
    assert [(e[0], e[3]) for e in events] == [("test", {"n": 2})]
    assert sp.seconds == pytest.approx((events[0][2] - events[0][1]) / 1e9,
                                       abs=1e-3)


def test_profiler_changes_no_answer(tmp_path):
    graphs = _db()
    off = _miner(str(tmp_path / "off")).fit(graphs)
    on, _ = _traced(lambda: _miner(str(tmp_path / "on")).fit(graphs))
    assert on.levels == off.levels
    assert on.supports == off.supports

    def untimed(res):
        return [(s.level, s.n_candidates, s.n_frequent, s.overflow,
                 s.rebalanced, s.imbalance, s.escalations, s.survivor_cap,
                 s.retried) for s in res.stats]

    assert untimed(on) == untimed(off)


# ---------------------------------------------------------------------------
# named scopes of the device programs
# ---------------------------------------------------------------------------

def _level_programs(monkeypatch):
    """(lru key, argument specs) of every level program a fit runs."""
    seen = []
    orig = level_step._level_program

    def spying(*key):
        fn = orig(*key)

        def call(*args):
            seen.append((key, [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=a.sharding)
                               for a in args]))
            return fn(*args)
        return call

    monkeypatch.setattr(level_step, "_level_program", spying)
    Mirage(MirageConfig(minsup=4, n_partitions=2, max_size=3)).fit(_db())
    monkeypatch.setattr(level_step, "_level_program", orig)
    return seen


def _compiled_text(key, specs):
    # a fresh closure each time, so nothing comes from a jit cache
    return (level_step._level_program.__wrapped__(*key)
            .lower(*specs).compile().as_text())


def code_only(hlo: str) -> str:
    """The HLO text without op metadata and the debug tables after the
    computations."""
    code = hlo.split("\nFileNames\n")[0]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", code)


class NoScope(contextlib.ContextDecorator):
    """What ``jax.named_scope`` is replaced with to compile without
    scopes."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_level_program_carries_the_named_scopes(monkeypatch):
    key, specs = _level_programs(monkeypatch)[0]
    text = _compiled_text(key, specs)
    for scope in SCOPES:
        assert f"mirage/{scope}/" in text, scope


def test_retry_and_permute_programs_carry_their_scopes():
    mesh = MiningMesh.single_device()
    meta = jax.numpy.zeros((1, 5), jax.numpy.int32)
    pol = jax.numpy.full((1, 1, 4, 2, 2), -1, jax.numpy.int32)
    pmask = jax.numpy.zeros((1, 1, 4, 2), bool)
    src = jax.numpy.full((1, 1, 4, 3), -1, jax.numpy.int32)
    emask = jax.numpy.zeros((1, 1, 4, 3), bool)
    text = (_materialize_program(mesh, 2, None)
            .lower(meta, pol, pmask, src, src, emask).compile().as_text())
    assert "mirage/materialize/" in text
    text = (level_step._permute_program(mesh)
            .lower(jax.numpy.zeros((1,), jax.numpy.int32), pol, pmask, src,
                   src, emask).compile().as_text())
    assert "mirage/permute/" in text


def test_named_scopes_leave_the_compiled_program_unchanged(monkeypatch):
    key, specs = _level_programs(monkeypatch)[0]
    scoped = _compiled_text(key, specs)
    monkeypatch.setattr(jax, "named_scope", lambda name: NoScope())
    plain = _compiled_text(key, specs)
    assert "mirage/" in scoped and "mirage/" not in plain
    assert code_only(scoped) == code_only(plain)


# ---------------------------------------------------------------------------
# the spill store's spans and scopes
# ---------------------------------------------------------------------------

def _adversarial():
    return random_db(8, n_vertices=8, extra_edge_prob=0.9, n_vlabels=1,
                     n_elabels=1, seed=7)


@pytest.mark.parametrize("store", ["dense", "spill"])
def test_level_and_fit_spans_count_the_spill_table(store):
    miner = Mirage(MirageConfig(minsup=4, n_partitions=2, max_size=3,
                                max_embeddings=2, embedding_store=store))
    graphs = _adversarial()
    miner.fit(graphs)
    res, events = _traced(lambda: miner.fit(graphs))
    levels = [e[3] for e in events if e[0] == "level"]
    assert [(a["spill_rows"], a["spill_pairs"]) for a in levels] == [
        (s.spill_rows, s.spill_pairs) for s in res.stats]
    fit = next(e[3] for e in events if e[0] == "fit")
    assert fit["spill_rows"] == sum(a["spill_rows"] for a in levels)
    assert fit["spill_pairs"] == sum(a["spill_pairs"] for a in levels)
    counted = [e[3] for e in events if e[0] == "spill_count"]
    if store == "dense":
        assert fit["spill_rows"] == fit["spill_pairs"] == 0
        assert counted == []
    else:
        assert fit["spill_rows"] > fit["spill_pairs"] > 0
        assert [a["rows"] for a in counted] == [
            s.spill_rows for s in res.stats if s.n_frequent]


_SPILL_PROGRAMS = ("_spill_support_program", "_spill_count_program",
                   "_spill_write_program")


def test_spill_ops_carry_their_own_scopes(monkeypatch):
    """Every op of the spill join and of the spill materialization is
    named by its own scope, and no other ``mirage/`` scope encloses it:
    the outermost scope of an op's name is the one it is put down to."""
    from repro.core import mapreduce

    seen = []
    for name in _SPILL_PROGRAMS:
        orig = getattr(mapreduce, name)

        def spying(*key, _orig=orig):
            fn = _orig(*key)

            def call(*args):
                seen.append((_orig, key, [
                    jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=a.sharding)
                    for a in args]))
                return fn(*args)
            return call

        monkeypatch.setattr(mapreduce, name, spying)
    Mirage(MirageConfig(minsup=4, n_partitions=2, max_size=3,
                        max_embeddings=2,
                        embedding_store="spill")).fit(_adversarial())
    monkeypatch.undo()
    names = set()
    for prog, key, specs in {id(p): (p, k, s) for p, k, s in seen}.values():
        text = prog.__wrapped__(*key).lower(*specs).compile().as_text()
        for op in re.findall(r'op_name="([^"]*)"', text):
            if "mirage/spill_" in op:
                first = re.search(r"(?:^|/)mirage/([A-Za-z_]+)", op)
                names.add(first.group(1))
    assert names == {"spill_support", "spill_materialize"}
