"""The control of ``nci1-ms20`` (edge occurrence lists cut at 30, half
the widest) comes out not correct through the harness's comparison
under the spill store, while the program as configured comes out
correct: at 64 graphs on the CPU."""
from _sub import drive


def test_spill_control_fails_the_comparison():
    rows = drive("nci1-ms20", 64, "--control")
    program = [r for r in rows if r["kind"] == "program"]
    control = [r for r in rows if r["kind"] == "control"]
    assert len(program) == 1 and len(control) == 2
    assert all(r["wrong_patterns"] == 0 for r in program)
    assert all(r["wrong_patterns"] > 0 for r in control)
