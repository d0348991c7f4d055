"""The harness as a whole: ``BENCHMARK.json`` and the files it names, the
entry point's refusals, and a traced run on the CPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from _sub import drive, drive_logged
from bench import cells

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves_to_its_files():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(cells.metric_reader(m["name"]).read)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
        assert cells.generator(cfg["generator"]).generate
        assert "max_occ" in cfg["control"]
    for w in SPEC["workloads"]:
        cell = cells.load_cell(w["name"], ROOT)
        assert cell.traffic["name"] == w["traffic"]
        assert cell.config["chips"] == w["chips"]
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)


def test_contract_shape():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    moves = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in moves for m in SPEC["per_layer"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def _run(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "aids-ms5",
         "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0 and out.stdout == ""


@pytest.mark.parametrize("cell", ["aids-ms5"])
def test_traced_run_on_cpu(cell):
    (res,) = drive(cell, 128, "--trace")
    assert res["correct"] is True
    m = res["metrics"]
    assert {"host_s", "redo_passes", "level_wait_s", "retry_s"} <= set(m)
    assert "fit_s" not in m
    assert m["level_wait_s"]["value"] > 0 and m["host_s"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("traced", [True, False], ids=["trace1", "trace0"])
def test_program_phases_are_read_only_after_the_measured_fit(traced):
    """``setup_s`` is read, and every fit (the warm-up and the measured
    ones) ends, before ``bench/phases.py`` is imported; a traced run
    then attaches the program's spans, and its metrics read them."""
    (res,), err = drive_logged("aids-ms5", 64, "--watch-imports",
                               *(["--trace"] if traced else []))
    lines = [ln for ln in err.splitlines() if ln.startswith("[drive] ")]
    setup = [ln for ln in lines if ln.startswith("[drive] setup: ")]
    fits = [i for i, ln in enumerate(lines) if "fit ended" in ln]
    read = [i for i, ln in enumerate(lines)
            if ln.startswith("[drive] program phases: ")]
    assert len(setup) == 1 and setup[0].endswith("[phases imported: False]")
    assert len(fits) >= 2
    assert all(lines[i].endswith("[phases imported: False]") for i in fits)
    assert res["correct"] is True
    m = res["metrics"]
    if not traced:
        assert read == [] and "phases imported: True" not in err
        assert set(m) == {"fit_s", "peak_hbm_gb", "setup_s"}
        return
    assert len(read) == 1 and read[0] > fits[-1]
    assert lines[read[0]].endswith("[phases imported: True]")
    # the CPU trace has the spans and counters; scopes and the kernel's
    # ops only a chip's trace has
    assert {"prep_s", "candgen_s", "wire_wait_s", "gc_s",
            "canon_tested"} <= set(m)
    assert m["canon_tested"]["value"] > 0 and m["prep_s"]["value"] > 0
    assert not {"materialize_ms", "kernel_least_roofline"} & set(m)


@pytest.mark.parametrize("cell", ["aids-ms5"])
def test_untraced_run_on_cpu(cell):
    (res,) = drive(cell, 128)
    assert res["correct"] is True and res["attempted"] >= 1
    assert set(res["metrics"]) == {"fit_s", "peak_hbm_gb", "setup_s"}
    assert res["metrics"]["fit_s"]["unit"] == "s"


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    from bench import harness

    x = harness.RunInputs(setup_s=1.0, window_s=1.0, fits=1, peak_bytes=1,
                          stats=[], fit_walls=[1.0], devices=[0], peaks=None)
    entry = [{"name": "retry_s", "unit": "s"}]
    with pytest.raises(RuntimeError, match="retry_s read nothing"):
        harness.read_metrics(entry, x)
    assert harness.read_metrics(entry, x, required=False) == {}
