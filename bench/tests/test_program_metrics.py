"""The per-layer metrics that read the program's own spans, counters and
device scopes (``RunInputs.phases``, from ``bench/phases.py``) and the
count of the support kernel's least bytes from the real embeddings,
on the chip traces of ``bench/testdata`` and on hand-made events; and
the metrics that were there before them, which read what they read
before on the same inputs."""
import math
from pathlib import Path

import pytest

from bench import cells, harness, phases, roofline, system, trace
from bench.gen import molecule
from bench.peaks import peaks
from bench.ref import miner

DATA = Path(__file__).resolve().parents[1] / "testdata"
OLD = DATA / "mol64.xplane.pb.gz"
SPANS = DATA / "mol64_spans.xplane.pb.gz"

#: the metrics that read ``RunInputs.phases``, and what each reads
PHASE_READINGS = {
    "prep_s": phases.prep_s,
    "candgen_s": phases.candgen_s,
    "wire_wait_s": phases.wire_wait_s,
    "gc_s": phases.gc_s,
    "canon_tested": phases.canon_tested,
    "materialize_ms": lambda ph: phases.materialize_ms(ph, [0]),
}
NEW = [*PHASE_READINGS, "kernel_least_roofline"]

#: the eight per-layer metrics before ``phases`` was attached, read by
#: the benchmark as it stood then on the inputs of :func:`_inputs`
BEFORE = {"host_s": 0.15250000000000002, "redo_passes": 2, "retry_s": 0.0,
          "level_wait_s": 0.1875, "kernel_ms": 6.920826,
          "kernel_hbm_roofline": 0.02968668916924226,
          "device_idle_share": 59.07890852941178,
          "device_other_ms": 132.210885}


def _read(name, x):
    return cells.metric_reader(name).read(x)


@pytest.fixture(scope="module")
def shapes():
    """The levels of ``record_trace.py``'s fit: 64 molecules at minsup
    30%, patterns of up to 3 edges (levels 2 and 3)."""
    ref = miner.mine(molecule.generate(64, 0), math.ceil(0.3 * 64))
    return roofline.level_shapes(ref, 64, 8, 32)[:2]


@pytest.fixture(scope="module")
def loaded():
    return {path: (trace.load(path), phases.load(path))
            for path in (OLD, SPANS)}


def _inputs(loaded, shapes, path=SPANS, with_phases=True):
    tr, ph = loaded[path]
    stats = [[{"map_seconds": 0.125, "escalations": 1, "retried": True},
              {"map_seconds": 0.0625, "escalations": 0, "retried": False}]]
    return harness.RunInputs(
        setup_s=30.5, window_s=0.34, fits=1, peak_bytes=123456789,
        stats=stats, fit_walls=[0.34], devices=[0],
        peaks=peaks("TPU v5 lite"), shapes=shapes, trace=tr,
        spanned=frozenset(s[3] for s in system.HOST_SPANS),
        phases=ph if with_phases else None)


@pytest.mark.parametrize("name", sorted(PHASE_READINGS))
def test_each_metric_reads_what_phases_reads(loaded, shapes, name):
    x = _inputs(loaded, shapes)
    assert _read(name, x) == PHASE_READINGS[name](x.phases)


def test_the_spans_trace_reads_its_phases(loaded, shapes):
    x = _inputs(loaded, shapes)
    assert _read("prep_s", x) == pytest.approx(0.1222, abs=1e-4)
    assert _read("candgen_s", x) == pytest.approx(0.0765, abs=1e-4)
    assert _read("materialize_ms", x) == pytest.approx(132.09, abs=0.01)
    assert _read("materialize_ms", x) <= _read("device_other_ms", x)
    assert _read("gc_s", x) == 0.0
    # recorded before the program counted its canonicality walks
    assert _read("canon_tested", x) is None


def test_canon_tested_is_the_fit_span_counter():
    ph = phases.Phases([("fit", 0, 100, {"canon_tested": 51890,
                                         "gc_s": 0.5}),
                        ("candgen", 10, 20, {"tested": 51890})], {}, False)
    x = harness.RunInputs(setup_s=1.0, window_s=1.0, fits=1, peak_bytes=1,
                          stats=[], fit_walls=[1.0], devices=[0],
                          peaks=None, phases=ph)
    assert _read("canon_tested", x) == 51890
    assert _read("gc_s", x) == 0.5


def test_least_roofline_counts_the_real_embeddings(loaded, shapes):
    x = _inputs(loaded, shapes)
    least = sum(roofline.least_support_bytes(s) for s in shapes)
    kernel_s = _read("kernel_ms", x) / 1e3
    got = _read("kernel_least_roofline", x)
    assert got == pytest.approx(100 * least / 819e9 / kernel_s, rel=1e-12)
    assert 0 < got <= _read("kernel_hbm_roofline", x) < 105


@pytest.mark.parametrize("name", sorted(PHASE_READINGS))
def test_a_trace_without_program_spans_reads_nothing(loaded, shapes, name):
    assert _read(name, _inputs(loaded, shapes, path=OLD)) is None
    assert _read(name, _inputs(loaded, shapes, with_phases=False)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_required_run_fails_where_a_new_metric_reads_nothing(name):
    x = harness.RunInputs(setup_s=1.0, window_s=1.0, fits=1, peak_bytes=1,
                          stats=[], fit_walls=[1.0], devices=[0], peaks=None)
    entry = [{"name": name, "unit": "x"}]
    with pytest.raises(RuntimeError, match=f"{name} read nothing"):
        harness.read_metrics(entry, x)
    assert harness.read_metrics(entry, x, required=False) == {}


@pytest.mark.parametrize("with_phases", [True, False],
                         ids=["phases", "no_phases"])
@pytest.mark.parametrize("name", sorted(BEFORE))
def test_earlier_metrics_read_as_before(loaded, shapes, name, with_phases):
    x = _inputs(loaded, shapes, with_phases=with_phases)
    assert _read(name, x) == BEFORE[name]


def test_dense_bytes_count_as_before(shapes):
    assert [roofline.support_bytes(s) for s in shapes] == [1137600, 545088]
