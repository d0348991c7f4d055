"""The per-layer metrics of the spill store (``spill_rows``, ``spill_ms``,
``support_least_roofline``) on hand-made program spans and device ops,
as ``bench/phases.py`` reads them from a trace: each reads what its
span arg or scopes hold, and nothing where they are absent."""
import math

import pytest

from bench import cells, harness, phases, roofline
from bench.gen import molecule
from bench.peaks import peaks
from bench.ref import miner

NEW = ["spill_rows", "spill_ms", "support_least_roofline"]


@pytest.fixture(scope="module")
def shapes():
    ref = miner.mine(molecule.generate(64, 0), math.ceil(0.3 * 64))
    return roofline.level_shapes(ref, 64, 8, 32)


def _inputs(ph, shapes=(), pk=None):
    return harness.RunInputs(
        setup_s=1.0, window_s=1.0, fits=1, peak_bytes=1, stats=[],
        fit_walls=[1.0], devices=[0], peaks=pk, shapes=list(shapes),
        phases=ph)


def _phases(ops, **fit_args):
    """A fit span and chip 0's ops as (scope, start, duration) in ns."""
    return phases.Phases(
        [("fit", 0, 10**9, fit_args)],
        {0: [(f"op{i}", s, d, scope) for i, (scope, s, d) in
             enumerate(ops)]},
        any(scope for scope, _s, _d in ops))


def _read(name, x):
    return cells.metric_reader(name).read(x)


def test_spill_rows_is_the_fit_span_counter():
    ph = _phases([], spill_rows=409857, spill_pairs=6123)
    assert _read("spill_rows", _inputs(ph)) == 409857


def test_spill_ms_sums_both_spill_scopes():
    ph = _phases([("support_kernel", 0, 5_000_000),
                  ("spill_support", 5_000_000, 2_000_000),
                  ("spill_materialize", 7_000_000, 3_000_000),
                  ("materialize", 10_000_000, 4_000_000)])
    assert _read("spill_ms", _inputs(ph)) == pytest.approx(5.0)


def test_support_roofline_reads_kernel_and_spill_join(shapes):
    least = sum(roofline.least_support_bytes(s) for s in shapes)
    pk = peaks("TPU v5 lite")
    least_ns = least / pk["hbm_bytes_per_s"] * 1e9
    # the support step takes twice its least time, split between the
    # dense kernel and the spill join
    ph = _phases([("support_kernel", 0, int(least_ns)),
                  ("spill_support", int(least_ns) + 10, int(least_ns)),
                  ("spill_materialize", 3 * int(least_ns), 1000)])
    got = _read("support_least_roofline", _inputs(ph, shapes, pk))
    assert got == pytest.approx(100 * least_ns / (2 * int(least_ns)),
                                rel=1e-9)
    assert 50.0 <= got < 51.0
    # at or above the least bytes' time it never reads over 100%
    ph = _phases([("support_kernel", 0, math.ceil(least_ns))])
    assert 0 < _read("support_least_roofline", _inputs(ph, shapes, pk)) \
        <= 100.0


@pytest.mark.parametrize("name", NEW)
def test_absent_spans_or_scopes_read_nothing(shapes, name):
    pk = peaks("TPU v5 lite")
    # no phases at all, a fit span without the arg, ops without scopes
    assert _read(name, _inputs(None, shapes, pk)) is None
    assert _read(name, _inputs(_phases([]), shapes, pk)) is None
    unscoped = _phases([("", 0, 1000)])
    assert _read(name, _inputs(unscoped, shapes, pk)) is None


@pytest.mark.parametrize("name", ["spill_ms", "support_least_roofline"])
def test_other_scopes_alone_read_nothing(shapes, name):
    """A dense-store program's trace (the parent's, say): scoped ops, but
    no spill scope — and for the roofline no support scope either."""
    ph = _phases([("materialize", 0, 1000), ("reduce", 1000, 10)])
    assert _read(name, _inputs(ph, shapes, peaks("TPU v5 lite"))) is None


@pytest.mark.parametrize("name", NEW)
def test_a_required_run_fails_where_a_spill_metric_reads_nothing(name):
    entry = [{"name": name, "unit": "x"}]
    with pytest.raises(RuntimeError, match=f"{name} read nothing"):
        harness.read_metrics(entry, _inputs(None))
