"""Chip smoke test: MIRAGE's main path end to end on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on a 2x2 host

One chip.  Builds ``pubchem_like_db`` (molecule-like graphs, ~26 edges
each, skewed atom labels) and mines it at minsup 10% to fixpoint with 8
partitions through ``Mirage.fit``, the entry point of
``python -m repro.launch.mine``:

``pipeline="single_sync"`` with the default config, twice (cold, then
warm from the in-process program caches), on ``--graphs`` graphs.
``pipeline="device_loop"`` is not run: its carried store holds one slot
per candidate of its budget, not per survivor, and at this DB size that
store alone is larger than the chip's memory (ROADMAP B3).

Four chips (``--chips 4``).  Only the single_sync run on a 4-worker mesh
with the default reduce_scatter shuffle and sharded wire; it checks
that every partition shard of the stores sits on its own chip.

Every run's frequent set and every support are compared bit for bit
with the host oracle ``mine_host`` on the same DB.  The script exits
non-zero, without printing a result, when JAX finds no TPU, when the
kernel path resolves to anything but the compiled fused kernel (packed
where the DB allows), or when any result differs from the oracle.  It
runs in one process and starts none.  The last line of stdout is the
JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: DB size: 10,000 graphs, cut from the 40,000 of a real
#: screening library — the uniform embedding cap escalates to M=128
#: (a few graphs hold >64 embeddings of one pattern), and the parent
#: store of a 128-pattern level at M=128 no longer fits 16 GB at 40,000
GRAPHS = 10_000
MINSUP = 0.1
PARTITIONS = 8


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def compare(res, ref, label: str) -> None:
    """Frequent set and every support bit for bit against the oracle."""
    want = {code: info.support for code, info in ref.frequent.items()}
    check([set(lv) for lv in res.levels] == [set(lv) for lv in ref.levels],
          f"{label}: frequent set differs from mine_host "
          f"({res.counts()} vs {[len(lv) for lv in ref.levels]})")
    check(dict(res.supports) == want,
          f"{label}: supports differ from mine_host")
    log(f"{label}: matches mine_host — {sum(res.counts())} patterns, "
        f"per level {res.counts()}")


def log_levels(res, label: str) -> None:
    for st in res.stats:
        log(f"{label} level {st.level}: candidates={st.n_candidates} "
            f"frequent={st.n_frequent} escalations={st.escalations} "
            f"retried={st.retried} survivor_cap={st.survivor_cap}")


def oracle(db):
    from repro.core.host_miner import mine_host

    t0 = time.perf_counter()
    ref = mine_host(db, math.ceil(MINSUP * len(db)))
    log(f"mine_host on {len(db)} graphs: {time.perf_counter() - t0:.2f}s, "
        f"per level {[len(lv) for lv in ref.levels]}, candidates "
        f"{ref.n_candidates}")
    return ref


def fit(miner, db, label: str):
    t0 = time.perf_counter()
    res = miner.fit(db)
    dt = time.perf_counter() - t0
    log(f"{label}: fit {dt:.2f}s")
    return res


def log_peak(label: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    log(f"{label}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"(bytes_limit {stats.get('bytes_limit')})")


def check_kernel_path(miner, n_graphs: int) -> None:
    backend, packed = miner.kernel_path(n_graphs)
    log(f"kernel path: backend={backend} packed={packed}")
    check(backend == "fused", f"backend resolved to {backend!r}, not the "
          f"compiled fused kernel")
    check(packed == (n_graphs < (1 << 16)),
          f"packed={packed} for a {n_graphs}-graph DB")


def single_chip(args) -> None:
    from repro.core.graphdb import pubchem_like_db
    from repro.core.mining import Mirage, MirageConfig

    db = pubchem_like_db(args.graphs, seed=args.seed)
    ref = oracle(db)
    miner = Mirage(MirageConfig(minsup=MINSUP, n_partitions=PARTITIONS))
    check_kernel_path(miner, len(db))
    res = fit(miner, db, "single_sync cold (compile included)")
    log_levels(res, "single_sync")
    compare(res, ref, "single_sync cold")
    res = fit(miner, db, "single_sync warm")
    compare(res, ref, "single_sync warm")
    log_peak("after single_sync")
    log("device_loop: not run — its store is sized by candidates, not "
        "survivors, and does not fit the chip at this DB size (ROADMAP B3)")


def four_chips(args) -> None:
    import jax

    from repro.core.graphdb import pubchem_like_db
    from repro.core.mapreduce import MiningMesh
    from repro.core.mining import Mirage, MirageConfig
    from repro.runtime import jax_compat

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    mesh = MiningMesh(jax_compat.make_mesh((4,), ("w",), devices=devices))
    db = pubchem_like_db(args.graphs, seed=args.seed)
    ref = oracle(db)
    miner = Mirage(MirageConfig(minsup=MINSUP, n_partitions=PARTITIONS),
                   mesh)
    check_kernel_path(miner, len(db))
    log(f"mesh: {mesh.n_workers} workers, reduce={miner.cfg.reduce}, "
        f"sharded wire={miner._sharded_wire()}")

    placed = []
    put = miner._device_put

    def spy(*arrays):
        out = put(*arrays)
        placed.append(out)
        return out

    miner._device_put = spy
    for label in ("4-chip single_sync cold (compile included)",
                  "4-chip single_sync warm"):
        res = fit(miner, db, label)
        log_levels(res, "4-chip single_sync")
        compare(res, ref, label)

    for name, arr in zip(("pol", "pmask", "src", "dst", "emask"),
                         placed[0]):
        shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
        spans = [(s.index[0].start, s.index[0].stop) for s in shards]
        owners = [s.device for s in shards]
        check(len(set(owners)) == 4 and set(owners) == set(devices),
              f"{name}: shards on {owners}, not one per chip")
        check(spans == [(2 * i, 2 * i + 2) for i in range(4)],
              f"{name}: partition spans {spans}")
        log(f"{name}: partitions {spans} on chips "
            f"{[d.id for d in owners]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--graphs", type=int, default=GRAPHS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")

    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else single_chip)(args)
    log_peak("end of run, device 0")
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as exc:
        print(f"[smoke] FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
