"""A run whose timed path is broken underneath comes out not correct, for
each fault a cell can have: a level step that hands back its state
unchanged, half of the graphs left out of support counting, and a
support altered where it comes off the wire.  No cell runs on more than
one chip, so none can leave out the exchange between chips.  The runs skip the look for a chip and
drive the rest of a benchmark run on the CPU at a small size."""
import pytest

from _sub import drive

N = 128


@pytest.mark.parametrize("cell", ["aids-ms5"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_makes_the_run_not_correct(cell, fault):
    (res,) = drive(cell, N, "--fault", fault)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("cell", ["aids-ms5"])
def test_unbroken_run_is_correct(cell):
    (res,) = drive(cell, N)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
