"""The control, the program with the configuration's ``control``
settings (edge occurrence lists cut at half the widest), comes out not
correct on every seed, while the program as configured comes out
correct: at a small size on the CPU.  ``bench/control.py`` reads the
same at each cell's own size on the chip."""
import pytest

from _sub import drive


@pytest.mark.parametrize("cell", ["aids-ms5"])
def test_control_fails_the_comparison(cell):
    rows = drive(cell, 256, "--control")
    program = [r for r in rows if r["kind"] == "program"]
    control = [r for r in rows if r["kind"] == "control"]
    assert len(program) == 1 and len(control) == 2
    assert all(r["wrong_patterns"] == 0 for r in program)
    assert all(r["wrong_patterns"] > 0 for r in control)
