"""The program's spans and device scopes read from a trace
(``bench/phases.py``): on hand-made events, on the small chip trace of
``test_trace.py`` (recorded before the program had spans), and on
``mol64_spans.xplane.pb.gz``: the fit of ``record_trace.py`` (64
molecules on a TPU v5 lite) once the program had spans and scopes,
recorded with ``ProfileOptions.enable_hlo_proto`` on.  ``record_trace.py``
as it stands records the same content: a TPU trace holds the programs'
HLO protos with that option off too."""
from pathlib import Path

import pytest

from bench import cells, phases, trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
OLD = DATA / "mol64.xplane.pb.gz"
SPANS = DATA / "mol64_spans.xplane.pb.gz"
KERNEL = cells.metric_reader("kernel_ms").KERNEL


def _hand_made():
    """A fit of two levels: prep, then per level candgen, metadata,
    schedule, a speculative candgen and the wait for the wire."""
    spans = [
        ("fit", 0, 1000, {"gc_s": 0.25, "levels": 2}),
        ("partition", 0, 40, {}), ("edge_ol_build", 40, 20, {}),
        ("level1", 60, 10, {}), ("upload", 70, 10, {}),
        ("level", 100, 400, {"level": 2}),
        ("candgen", 100, 50, {}), ("candidate_meta", 150, 10, {}),
        ("schedule", 160, 20, {}), ("dispatch", 180, 5, {}),
        ("candgen_spec", 185, 100, {}), ("wire_wait", 285, 100, {}),
        ("gc", 300, 50, {"collected": 7}),
        ("level", 500, 400, {"level": 3}),
        ("candgen", 500, 200, {}), ("wire_wait", 700, 150, {}),
    ]
    ops = {0: [("%fusion.1 = s32[8] fusion()", 190, 90, "materialize"),
               ("%fusion.2 = s32[8] fusion()", 250, 60, "materialize"),
               ("%kernel.3 = s32[8] custom-call()", 720, 100,
                "support_kernel")]}
    return phases.Phases(spans, ops, True)


def test_scope_is_the_component_after_mirage():
    assert phases._scope(
        "jit(core)/jit(main)/shard_map/mirage/materialize/jit(_where)/"
        "select_n") == "materialize"
    assert phases._scope("jit(core)/mirage/wire_pack/concatenate") == \
        "wire_pack"
    assert phases._scope("jit(_pad)/pad") == ""


@pytest.mark.parametrize("reader,expected", [
    (phases.prep_s, 80e-9),
    (phases.candgen_s, 280e-9),
    (phases.spec_candgen_s, 100e-9),
    (phases.wire_wait_s, 250e-9),
    (phases.gc_s, 0.25),
])
def test_span_readings_sum_their_phases(reader, expected):
    assert reader(_hand_made()) == pytest.approx(expected)


def test_materialize_ms_is_the_busy_time_of_its_scope():
    # two overlapping ops under mirage/materialize: 190..310
    assert phases.materialize_ms(_hand_made(), [0]) == pytest.approx(120e-6)


def test_readings_are_none_without_program_spans_or_scopes():
    bare = phases.Phases([], {0: [("%a.1 = s32[] add()", 0, 5, "")]},
                         False)
    for reader in (phases.prep_s, phases.candgen_s, phases.spec_candgen_s,
                   phases.wire_wait_s, phases.gc_s, phases.coverage):
        assert reader(bare) is None
    assert phases.materialize_ms(bare, [0]) is None


def test_coverage_is_the_union_of_leaf_spans_in_the_fit():
    # leaves cover 0..80, 100..385 and 500..850 of the fit's 1000 ns
    assert phases.coverage(_hand_made()) == pytest.approx(0.715)


def test_labels_carry_the_level():
    ph = _hand_made()
    by = phases.by_label(ph)
    assert by["L3:candgen"] == pytest.approx(200e-9)
    assert by["partition"] == pytest.approx(40e-9)
    assert "fit" not in by and "L2:level" not in by


def test_idle_gaps_named_by_the_leaf_span_covering_most_and_its_level():
    ph = _hand_made()
    ops = [op[:3] for op in ph.ops[0]]
    bench_spans = [("fit", 0, 1000), ("candgen", 0, 1000)]
    gaps = phases.idle_gaps(ph, ops, bench_spans, window=(0, 1000))
    assert gaps[0] == ["L3:candgen", 410e-9]      # 310..720
    assert gaps[1] == ["L2:candgen", 190e-9]      # 0..190: 50 of it
    assert gaps[2] == ["L3:wire_wait", 180e-9]    # 820..1000
    # a gap no program span covers keeps the benchmark's name
    lone = phases.Phases([("fit", 0, 1000, {})], {}, False)
    assert phases.idle_gaps(lone, ops, bench_spans, window=(0, 1000)) == \
        trace.idle_gaps(ops, bench_spans, window=(0, 1000))


def test_old_trace_has_no_program_spans_and_keeps_its_gap_names():
    ph = phases.load(OLD)
    tr = trace.load(OLD)
    assert ph.spans == [] and not ph.scoped
    assert phases.prep_s(ph) is None
    assert phases.materialize_ms(ph, [0]) is None
    fit = [s for s in tr.spans if s[0] == "fit"][0]
    window = (fit[1], fit[1] + fit[2])
    assert phases.idle_gaps(ph, tr.devices[0], tr.spans,
                            window=window) == \
        trace.idle_gaps(tr.devices[0], tr.spans, window=window)


@pytest.fixture(scope="module")
def spans_trace():
    return phases.load(SPANS), trace.load(SPANS)


def test_spans_trace_holds_the_fits_phases_and_level_args(spans_trace):
    ph, _ = spans_trace
    assert {"fit", "partition", "edge_ol_build", "level1", "upload",
            "level", "candgen", "candidate_meta", "schedule", "dispatch",
            "wire_wait", "wire_decode", "audit"} <= {s[0] for s in ph.spans}
    levels = [s[3] for s in ph.spans if s[0] == "level"]
    assert [(a["level"], a["candidates"], a["spec"]) for a in levels] == [
        (2, 315, "skipped"), (3, 225, "skipped")]
    fit = next(s[3] for s in ph.spans if s[0] == "fit")
    assert (fit["levels"], fit["compiles"], fit["wire_fetches"]) == (3, 0, 2)
    assert phases.coverage(ph) > 0.95


def test_every_op_of_the_level_programs_is_scoped(spans_trace):
    """Only the compiler's own copies go without a scope; the kernel is
    under mirage/support_kernel."""
    ph, _ = spans_trace
    ops = ph.ops[0]
    scoped = trace.busy_ns(op[:3] for op in ops if op[3])
    assert scoped >= 0.99 * trace.busy_ns(op[:3] for op in ops)
    kernels = [op for op in ops if KERNEL.match(trace.op_name(op[0]))]
    assert kernels and {op[3] for op in kernels} == {"support_kernel"}


def test_readings_on_the_spans_trace(spans_trace):
    """The fit of 64 molecules: materialization holds most of the
    device's time, the kernel a twentieth of it."""
    ph, tr = spans_trace
    mat = phases.materialize_ms(ph, [0])
    kernel = phases.scope_ns(ph, [0], "support_kernel") / 1e6
    assert mat == pytest.approx(132.09, abs=0.01)
    assert kernel == pytest.approx(6.99, abs=0.01)
    assert mat + kernel <= trace.busy_ns(tr.devices[0]) / 1e6
    assert phases.prep_s(ph) == pytest.approx(0.1222, abs=1e-4)
    assert phases.candgen_s(ph) == pytest.approx(0.0765, abs=1e-4)
    assert phases.spec_candgen_s(ph) == 0.0
    assert phases.gc_s(ph) == 0.0


def test_idle_gaps_of_the_spans_trace_name_a_program_span(spans_trace):
    ph, tr = spans_trace
    fit = next(s for s in ph.spans if s[0] == "fit")
    gaps = phases.idle_gaps(ph, tr.devices[0], tr.spans,
                            window=(fit[1], fit[1] + fit[2]))
    assert [g[0] for g in gaps[:3]] == ["level1", "L3:candgen",
                                        "L3:wire_wait"]
    assert all(g[0] == "level1" or g[0][:3] in ("L2:", "L3:")
               for g in gaps)
