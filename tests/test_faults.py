"""Unit tests for the chaos layer's parts in isolation (DESIGN.md §10):
fault schedules, the wire checksum, checkpoint integrity + fallback,
graph-DB validation, the donation re-arming state machine, and the
supervisor's classifier/shrink policy.  End-to-end fault recovery lives
in test_chaos.py."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import supervisor as sup_mod
from repro.core.graphdb import (Graph, GraphValidationError, paper_toy_db,
                                validate_db)
from repro.core.level_step import wire_checksum
from repro.core.mining import DonationPolicy
from repro.core.partition import make_partitions
from repro.runtime import checkpoint as ckpt
from repro.runtime import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_log()
    yield
    faults.clear()
    faults.reset_log()


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def test_fault_spec_parse_grammar():
    s = faults.FaultSpec.parse("kernel_fault@3*4")
    assert (s.kind, s.level, s.times) == ("kernel_fault", 3, 4)
    s = faults.FaultSpec.parse("wire_bitflip@2:word=5,bit=12")
    assert (s.level, s.word, s.bit) == (2, 5, 12)
    s = faults.FaultSpec.parse("ckpt_corrupt@2:mode=truncate")
    assert s.mode == "truncate"
    with pytest.raises(ValueError):
        faults.FaultSpec.parse("worker_loss")           # no @level
    with pytest.raises(ValueError):
        faults.FaultSpec.parse("frobnicate@2")          # unknown kind
    with pytest.raises(ValueError):
        faults.FaultSpec.parse("worker_loss@2:color=3")  # unknown option
    with pytest.raises(ValueError):
        faults.FaultSpec.parse("ckpt_corrupt@2:mode=nope")


def test_fault_schedule_parse_and_describe():
    sched = faults.FaultSchedule.parse(
        "worker_loss@2; kernel_fault@3*2 ;wire_bitflip@4:bit=3")
    assert [s.kind for s in sched.specs] == [
        "worker_loss", "kernel_fault", "wire_bitflip"]
    assert "kernel_fault@3*2" in sched.describe()


def test_random_schedule_is_seed_deterministic():
    a = faults.FaultSchedule.random(123, max_level=5, n_faults=3)
    b = faults.FaultSchedule.random(123, max_level=5, n_faults=3)
    assert [vars(s) for s in a.specs] == [vars(s) for s in b.specs]
    c = faults.FaultSchedule.random(124, max_level=5, n_faults=3)
    assert [vars(s) for s in a.specs] != [vars(s) for s in c.specs]
    for s in a.specs:
        assert s.kind in faults.KINDS and s.level >= 2


def test_schedule_fires_exactly_times_and_logs():
    with faults.active(faults.FaultSchedule.parse("worker_loss@2*2")):
        for _ in range(2):
            with pytest.raises(faults.WorkerLost):
                faults.maybe_raise("level_start", 2)
        faults.maybe_raise("level_start", 2)            # budget exhausted
        faults.maybe_raise("level_start", 3)            # wrong level
    log = faults.injection_log()
    assert [(e["kind"], e["level"]) for e in log] == [
        ("worker_loss", 2), ("worker_loss", 2)]
    # re-install re-arms the budgets
    sched = faults.FaultSchedule.parse("worker_loss@2")
    with faults.active(sched):
        with pytest.raises(faults.WorkerLost):
            faults.maybe_raise("level_start", 2)
    with faults.active(sched):
        with pytest.raises(faults.WorkerLost):
            faults.maybe_raise("level_start", 2)


def test_hooks_are_noops_without_schedule():
    faults.maybe_raise("level_start", 2)
    faults.maybe_raise("kernel", 2)
    w = np.arange(8, dtype=np.int32)
    assert faults.corrupt_wire(w, 2) is w
    assert faults.override_cap(17, 2) == 17
    assert faults.injection_log() == []


# ---------------------------------------------------------------------------
# wire checksum
# ---------------------------------------------------------------------------

def test_wire_checksum_host_device_agree():
    body = np.arange(-7, 50, dtype=np.int32) * 92821
    assert int(wire_checksum(body)) == int(wire_checksum(jnp.asarray(body)))
    v = int(wire_checksum(body))
    assert -2**31 <= v < 2**31


def test_wire_checksum_detects_flips_and_swaps():
    body = np.arange(64, dtype=np.int32)
    ref = int(wire_checksum(body))
    for word, bit in [(0, 0), (31, 7), (63, 30)]:
        bad = body.copy()
        bad[word] ^= np.int32(1 << bit)
        assert int(wire_checksum(bad)) != ref
    swapped = body.copy()
    swapped[[3, 40]] = swapped[[40, 3]]
    assert int(wire_checksum(swapped)) != ref


def test_corrupt_wire_flips_scheduled_bit_in_a_copy():
    wire = np.zeros(16, np.int32)
    with faults.active(faults.FaultSchedule.parse(
            "wire_bitflip@2:word=5,bit=3")):
        out = faults.corrupt_wire(wire, 2)
    assert out is not wire and wire[5] == 0
    assert out[5] == 1 << 3 and (np.delete(out, 5) == 0).all()
    # word out of range falls back to the middle word
    with faults.active(faults.FaultSchedule.parse(
            "wire_bitflip@2:word=99")):
        out = faults.corrupt_wire(wire, 2)
    assert out[8] != 0


# ---------------------------------------------------------------------------
# checkpoint integrity
# ---------------------------------------------------------------------------

def _tree():
    return {"a": np.arange(6, dtype=np.int32).reshape(2, 3),
            "b": [np.ones(4, np.float32), 7],
            "c": "label"}


def test_checkpoint_roundtrip_with_digests(tmp_path):
    p = str(tmp_path / "ck")
    ckpt.save_pytree(p, _tree(), metadata={"x": 1})
    with open(os.path.join(p, "manifest.json")) as f:
        man = json.load(f)
    assert len(man["digests"]) == man["n_leaves"] == 2
    tree, meta = ckpt.load_pytree(p)
    np.testing.assert_array_equal(tree["a"], _tree()["a"])
    assert meta == {"x": 1}


@pytest.mark.parametrize("mode", ["flip", "truncate", "manifest"])
def test_damaged_checkpoint_raises_integrity_error(tmp_path, mode):
    p = str(tmp_path / "ck")
    ckpt.save_pytree(p, _tree())
    faults.damage_checkpoint(p, mode)
    with pytest.raises(ckpt.CheckpointIntegrityError):
        ckpt.load_pytree(p)


def test_load_step_falls_back_to_newest_intact_and_reaps(tmp_path):
    root = str(tmp_path)
    for step in (1, 2, 3):
        ckpt.save_step(root, step, {"v": np.full(3, step)})
    faults.damage_checkpoint(os.path.join(root, "step_0000000003"), "flip")
    tree, meta = ckpt.load_step(root)
    assert meta["step"] == 2 and tree["v"][0] == 2
    assert ckpt.all_steps(root) == [1, 2]        # corrupt step reaped
    # explicit step stays strict
    faults.damage_checkpoint(os.path.join(root, "step_0000000002"),
                             "truncate")
    with pytest.raises(ckpt.CheckpointIntegrityError):
        ckpt.load_step(root, 2)


def test_load_step_raises_when_everything_is_corrupt(tmp_path):
    root = str(tmp_path)
    ckpt.save_step(root, 1, {"v": np.zeros(2)})
    faults.damage_checkpoint(os.path.join(root, "step_0000000001"),
                             "manifest")
    with pytest.raises(FileNotFoundError):
        ckpt.load_step(root)
    assert ckpt.latest_step(root) is None


def test_latest_step_reaps_tmp_dirs_and_incomplete_steps(tmp_path):
    root = str(tmp_path)
    ckpt.save_step(root, 4, {"v": np.zeros(2)})
    os.makedirs(os.path.join(root, ".tmp.ckpt.dead-writer"))
    incomplete = os.path.join(root, "step_0000000005")
    os.makedirs(incomplete)                      # no manifest/payload
    assert ckpt.latest_step(root) == 4
    assert not os.path.exists(incomplete)
    assert not any(n.startswith(".tmp.") for n in os.listdir(root))


def test_scheduled_ckpt_corruption_hits_matching_step_only(tmp_path):
    root = str(tmp_path)
    with faults.active(faults.FaultSchedule.parse(
            "ckpt_corrupt@2:mode=flip")):
        ckpt.save_step(root, 1, {"v": np.zeros(2)})
        ckpt.save_step(root, 2, {"v": np.ones(2)})
    ckpt.load_step(root, 1)
    with pytest.raises(ckpt.CheckpointIntegrityError):
        ckpt.load_step(root, 2)


# ---------------------------------------------------------------------------
# graph-DB validation
# ---------------------------------------------------------------------------

def _g(vl, e, el):
    return Graph(np.asarray(vl), np.asarray(e).reshape(-1, 2),
                 np.asarray(el))


def test_validate_db_accepts_real_databases():
    validate_db(paper_toy_db())


@pytest.mark.parametrize("bad,msg", [
    (_g([], np.empty((0, 2)), []), "no vertices"),
    (_g([0, 1], [(0, 2)], [0]), "dangling"),
    (_g([0, 1], [(0, -1)], [0]), "dangling"),
    (_g([0, -2], [(0, 1)], [0]), "negative vertex label"),
    (_g([0, 1], [(0, 1)], [-1]), "negative edge label"),
    (_g([0, 1], [(0, 1)], [0, 0]), "edge labels"),
    (_g([0, 1], [(0, 0)], [0]), "self-loop"),
    (_g([0, 1, 2], [(0, 1), (1, 0)], [0, 0]), "duplicate"),
    # whole DBs: the defect in a later graph, or two bad graphs of which
    # the first is named, with the edge at fault
    ([*paper_toy_db(), *paper_toy_db(),
      _g([0, 1, 2], [(0, 1), (1, 2), (0, 3)], [0, 0, 0])],
     r"^graph 6, edge 2: dangling edge endpoint 3 outside \[0, 3\)$"),
    ([*paper_toy_db(), _g([0, 1, 2], [(1, 2), (0, 1), (1, 2)], [0, 0, 0]),
      _g([0, 1], [(0, 5)], [0])],
     r"^graph 3, edge 0: duplicate edge \(1, 2\)"),
    ([*paper_toy_db(), paper_toy_db()[1],
      _g([0, 1, 2], [(0, 1), (2, 2)], [0, 0]), _g([0, -4], [], [])],
     r"^graph 4, edge 1: self-loop at vertex 2$"),
    ([*paper_toy_db(), paper_toy_db()[2],
      _g([0, 1, 2], [(0, 1), (1, 2), (0, 2)], [0, 1, -3]),
      _g([0, 1], [(0, 1)], [-1])],
     r"^graph 4, edge 2: negative edge label -3$"),
    ([*paper_toy_db(), _g([0, -2], [(0, 1)], [0]), _g([], [], [])],
     r"^graph 3: negative vertex label -2$"),
    ([paper_toy_db()[0], _g([0, 1], [(0, 1)], [0, 0]),
      _g([0, 1], [(0, 0)], [0])],
     r"^graph 1: 1 edges but 2 edge labels$"),
])
def test_validate_db_rejects_malformed_graphs(bad, msg):
    db = bad if isinstance(bad, list) else [paper_toy_db()[0], bad]
    with pytest.raises(GraphValidationError, match=msg):
        validate_db(db)


def test_validate_db_rejects_empty_database():
    with pytest.raises(GraphValidationError, match="empty database"):
        validate_db([])


def test_make_partitions_validates_at_the_load_boundary():
    graphs = paper_toy_db() + [_g([0, 1], [(0, 5)], [0])]
    with pytest.raises(GraphValidationError, match="graph 3"):
        make_partitions(graphs, 2, 2)
    # filtering that empties graphs internally stays legal: minsup high
    # enough that every edge is dropped must NOT raise
    make_partitions(paper_toy_db(), 3, 1)


# ---------------------------------------------------------------------------
# donation re-arming state machine
# ---------------------------------------------------------------------------

def test_donation_policy_arms_after_k_clean_levels():
    pol = DonationPolicy(3, can_rebuild=False)
    for _ in range(5):
        pol.record(retried=False)
    assert not pol.armed                 # no checkpoint -> never arms
    pol.can_rebuild = True
    assert pol.armed
    pol.record(retried=True)             # a retry resets the streak
    assert not pol.armed
    pol.record(False), pol.record(False)
    assert not pol.armed                 # 2 < k
    pol.record(False)
    assert pol.armed


def test_donation_policy_rebuild_resets_streak():
    pol = DonationPolicy(1, can_rebuild=True)
    pol.record(False)
    assert pol.armed
    pol.record_rebuild()
    assert pol.rebuilds == 1 and not pol.armed
    pol.record(False)
    assert pol.armed


def test_donation_policy_zero_k_never_arms():
    pol = DonationPolicy(0, can_rebuild=True)
    for _ in range(10):
        pol.record(False)
    assert not pol.armed


# ---------------------------------------------------------------------------
# supervisor policy units
# ---------------------------------------------------------------------------

def test_classify_maps_taxonomy_to_recovery_classes():
    assert sup_mod.classify(faults.WorkerLost(2, 1)) == "worker_loss"
    assert sup_mod.classify(faults.KernelFault(3)) == "kernel"
    assert sup_mod.classify(faults.WireIntegrityError("x")) == "transient"
    assert sup_mod.classify(faults.CheckpointIntegrityError("x")) == "state"
    assert sup_mod.classify(faults.HangTimeout(3, 0.5)) == "hang"
    assert sup_mod.classify(faults.AuditError(2, "bad word")) == "state"
    assert sup_mod.classify(ValueError("real bug")) is None


def test_elastic_shrink_picks_largest_divisor():
    assert sup_mod.elastic_shrink(4, 12) == 3
    assert sup_mod.elastic_shrink(4, 8) == 2
    assert sup_mod.elastic_shrink(2, 8) == 1
    assert sup_mod.elastic_shrink(1, 8) is None           # nothing below 1
    assert sup_mod.elastic_shrink(4, 8, min_workers=3) is None
    assert sup_mod.elastic_shrink(8, 7) == 7              # 7 | 7


def test_supervisor_reraises_fatal_and_exhausted_budget(tmp_path):
    from repro.core.mining import MirageConfig
    log = tmp_path / "faults.json"
    sup = sup_mod.MiningSupervisor(
        MirageConfig(minsup=2, n_partitions=2, max_size=3),
        sup_mod.SupervisorConfig(max_retries=2, sleep_fn=lambda s: None,
                                 fault_log_path=str(log)))
    # unclassified exceptions are fatal: surface immediately, once
    with faults.active(faults.FaultSchedule.parse("worker_loss@2*99")):
        with pytest.raises(faults.WorkerLost):
            sup.mine(paper_toy_db())
    assert [e.action for e in sup.events][-1] == "give_up"
    assert len([e for e in sup.events if e.action != "give_up"]) == 2
    # crash-safe JSONL: one line per event the moment it happened,
    # plus the end-of-run summary line
    lines = [json.loads(l) for l in log.read_text().splitlines()]
    events = [l for l in lines if "summary" not in l]
    assert len(events) == len(sup.events)
    assert lines[-1]["summary"]["outcome"] == "exhausted"
    assert lines[-1]["summary"]["by_kind"] == {"worker_loss": 2}


def test_supervisor_passes_fatal_through():
    from repro.core.mining import MirageConfig
    sup = sup_mod.MiningSupervisor(
        MirageConfig(minsup=2, n_partitions=2, max_size=3),
        sup_mod.SupervisorConfig(sleep_fn=lambda s: None))
    bad_db = paper_toy_db() + [_g([0, 1], [(0, 7)], [0])]
    with pytest.raises(GraphValidationError, match="dangling"):
        sup.mine(bad_db)             # a real input bug is NOT retried
    assert [e.kind for e in sup.events] == ["fatal"]
