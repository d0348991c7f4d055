"""Readings that set the limits of the comparison with the reference.

    python bench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--out FILE]

In one process (the set-up is long, and every seed's DB has the same
shapes, so the programs compile once): for each of ``--seeds`` one fit
of the program as the cell runs it, and for each of ``--control-seeds``
one fit of the control, each compared with the plain reference on the
seed's DB: ``wrong_patterns``, as the benchmark's runs compare it,
and its parts.  The control is the program with the configuration's
``control`` settings (``max_occ``: edge occurrence lists cut at half the
widest), which breaks the configuration's guarantee of supports counted
over every occurrence.  Each reading is one JSON line: the cell, the
seed, ``program`` or ``control``, and the numbers compared.  Not run by
the benchmark's own runs.
"""
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(f"[control] {msg}", file=sys.stderr, flush=True)


def readings(workload: str, seeds, control_seeds, *, root: Path,
             require_tpu: bool = True, n_graphs=None):
    """Yield one reading per seed: program seeds first, then control."""
    from bench import cells, harness, system

    cell = cells.load_cell(workload, root)
    devices, _ = harness.pick_devices(cell.chips, require_tpu)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctrl_cfg = dict(cell.config)
    ctrl_cfg["miner"] = {**cell.config.get("miner", {}),
                         **cell.config["control"]}
    for kind, cfg, run_seeds in (("program", cell.config, seeds),
                                 ("control", ctrl_cfg, control_seeds)):
        miner = system.build_miner(cfg, cell.traffic, devices)
        if require_tpu and miner.kernel_path(1)[0] != "fused":
            raise harness.NoDevice("not the compiled fused kernel")
        for seed in run_seeds:
            db = harness.make_db(cell, seed, n_graphs)
            t = time.perf_counter()
            ans = system.answer(miner.fit(system.to_graphs(db)))
            fit_s = time.perf_counter() - t
            diff = harness.compare(ans, harness.reference(cell, db))
            wrong = diff["missing"] + diff["extra"] + diff["wrong_support"]
            yield {"cell": workload, "seed": seed, "kind": kind,
                   "fit_s": fit_s, "wrong_patterns": wrong, **diff}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    parse = (lambda s: [int(x) for x in s.split(",") if x])
    try:
        for r in readings(args.workload, parse(args.seeds),
                          parse(args.control_seeds), root=ROOT):
            line = json.dumps(r)
            print(line, flush=True)
            if args.out is not None:
                args.out.parent.mkdir(parents=True, exist_ok=True)
                with args.out.open("a") as f:
                    f.write(line + "\n")
    except harness.NoDevice as exc:
        log(f"FAILED: {exc}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
