"""The reduction from a profiler trace to device metrics: on hand-made
events, and on a small trace recorded on a TPU v5 lite
(``record_trace.py``: 64 molecules, minsup 30%, 3-edge patterns)."""
import dataclasses
import re
from pathlib import Path

import pytest

from bench import cells, harness, trace

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "mol64.xplane.pb.gz"
KERNEL = cells.metric_reader("kernel_ms").KERNEL
COLLECTIVE = cells.metric_reader("collective_ms").COLLECTIVE


def test_union_and_self_time_of_nested_ops():
    ops = [("%while.1 = (s32[]) while(%t)", 0, 100),
           ("%fusion.2 = s32[8] fusion(%a)", 10, 30),
           ("%fusion.3 = s32[8] fusion(%b)", 50, 20),
           ("%copy.4 = s32[8] copy(%c)", 150, 10)]
    assert trace.intervals(ops) == [(0, 100), (150, 160)]
    assert trace.busy_ns(ops) == 110
    assert trace.self_ns(ops) == [50, 30, 20, 10]
    top = dict(trace.top_ops(ops, width=8))
    assert top["%while.1"] == pytest.approx(50e-9)


def test_kernel_and_collectives_match_by_name_not_by_operand():
    kernel = ("%fused_level_packed_pallas.1 = (s32[8,1024,8,128]) "
              "custom-call(s32[49152] %reshape.0)", 0, 7)
    user = ("%fusion.9 = s32[8] fusion(s32[8] %fused_level_packed_pallas.1)",
            7, 3)
    coll = ("%all-gather-start.2 = s32[4] all-gather-start(s32[1] %x)", 10, 2)
    rs = ("%fusion.5 = s32[2] reduce-scatter(s32[8] %y)", 12, 4)
    ops = [kernel, user, coll, rs]
    assert trace.matching(ops, KERNEL) == [kernel]
    assert trace.matching(ops, COLLECTIVE) == [coll, rs]
    assert trace.op_name(kernel[0]) == "fused_level_packed_pallas.1"
    assert trace.op_code(kernel[0]) == "custom-call"


def test_idle_gaps_are_named_by_the_host_phase_in_them():
    ops = [("%a.1 = s32[] add()", 100, 100), ("%b.2 = s32[] add()", 500, 100)]
    spans = [("fit", 0, 1000), ("candgen", 210, 280), ("partition", 0, 90)]
    gaps = trace.idle_gaps(ops, spans, window=(0, 1000))
    assert gaps[0] == ["host outside any phase span", 400e-9]
    assert gaps[1] == ["candgen", 300e-9]
    assert gaps[2] == ["partition", 100e-9]


def test_small_chip_trace():
    tr = trace.load(SMALL)
    assert sorted(tr.devices) == [0]
    ops = tr.devices[0]
    fit = [s for s in tr.spans if s[0] == "fit"]
    assert len(fit) == 1
    _, start, length = fit[0]
    assert {"partition", "candgen", "dispatch", "fetch_decode"} <= {
        s[0] for s in tr.spans}
    busy = trace.busy_ns(ops)
    assert 0 < busy < length
    kernels = trace.matching(ops, KERNEL)
    # one support kernel per level program: levels 2 and 3
    assert len(kernels) == 2
    assert all(trace.op_code(k[0]) == "custom-call" for k in kernels)
    assert trace.matching(ops, COLLECTIVE) == []    # one chip
    assert all(t >= 0 for t in trace.self_ns(ops))
    own = sum(s for _, s in trace.top_ops(ops, n=len(ops)))
    assert own == pytest.approx(busy / 1e9, rel=1e-6)
    gaps = trace.idle_gaps(ops, tr.spans, window=(start, start + length))
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in gaps) <= (length - busy) / 1e9 + 1e-9


def test_metric_readers_on_the_small_chip_trace():
    tr = trace.load(SMALL)
    fit = [s for s in tr.spans if s[0] == "fit"][0]
    x = harness.RunInputs(
        setup_s=1.0, window_s=fit[2] / 1e9, fits=1, peak_bytes=1,
        stats=[[{"map_seconds": 0.0, "escalations": 0, "retried": False}]],
        fit_walls=[fit[2] / 1e9], devices=[0],
        peaks={"hbm_bytes_per_s": 819e9}, trace=tr,
        spanned=frozenset({"retry_materialize"}))
    read = {m: cells.metric_reader(m).read(x) for m in
            ("kernel_ms", "collective_ms", "device_idle_share",
             "device_other_ms", "retry_s")}
    assert read["kernel_ms"] > 0
    assert read["collective_ms"] is None
    assert 0 < read["device_idle_share"] < 100
    busy_ms = trace.busy_ns(tr.devices[0]) / 1e6
    assert read["device_other_ms"] == pytest.approx(busy_ms
                                                    - read["kernel_ms"])
    assert read["retry_s"] >= 0
    # no level shapes, so no roofline share: nothing, never 0
    assert cells.metric_reader("kernel_hbm_roofline").read(x) is None
    # the retry span not put in place: nothing, not 0 s
    unspanned = dataclasses.replace(x, spanned=frozenset())
    assert cells.metric_reader("retry_s").read(unspanned) is None


def test_a_trace_without_the_kernels_name_fails_the_run():
    tr = trace.Trace({0: [("%fusion.1 = s32[8] fusion(%a)", 0, 5)]}, [])
    x = harness.RunInputs(setup_s=1.0, window_s=1.0, fits=1, peak_bytes=1,
                          stats=[], fit_walls=[1.0], devices=[0], peaks=None,
                          trace=tr)
    assert cells.metric_reader("kernel_ms").read(x) is None
    with pytest.raises(RuntimeError, match="kernel_ms read nothing"):
        harness.read_metrics([{"name": "kernel_ms", "unit": "ms"}], x)


def test_chip_mean_reads_nothing_without_the_chips():
    tr = trace.Trace({0: [("%a.1 = s32[] add()", 0, 5)]}, [])
    assert trace.chip_mean(tr, [1], trace.busy_ns) is None
    assert trace.chip_mean(None, [0], trace.busy_ns) is None
    assert trace.chip_mean(tr, [0, 1], trace.busy_ns) == 5
    assert re.match(KERNEL, "fused_level_pallas.12")
