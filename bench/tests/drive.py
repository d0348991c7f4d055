"""Drive a benchmark run on the CPU at a small size, with a fault planted
in the timed path, for the tests.

    python bench/tests/drive.py CELL N_GRAPHS [--trace] [--fault NAME]
                                [--watch-imports]
    python bench/tests/drive.py CELL N_GRAPHS --control

The harness's look for a chip is skipped (``require_tpu=False``) and the
DB is cut to ``N_GRAPHS``; everything else runs as in a benchmark run.
A four-chip cell gets four virtual CPU devices.  Prints the result
line, or with ``--control`` one line per control reading.  With
``--watch-imports`` every message of the harness, and the end of every
fit, is logged with whether ``bench.phases`` was imported by then.
"""
import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def state_unchanged():
    """Each level step hands back the state it was given: the parents'
    store, and no new frequent patterns."""
    from repro.core import mining

    orig = mining.Mirage._level_single_sync

    def level(self, meta_p, meta, C, pol, pmask, *args, **kwargs):
        out = orig(self, meta_p, meta, C, pol, pmask, *args, **kwargs)
        return dataclasses.replace(out, pol=pol, pmask=pmask,
                                   keep=out.keep[:0])

    mining.Mirage._level_single_sync = level


def half_batch():
    """Support counting sees only half of the graphs of each partition."""
    from repro.core import mining

    orig = mining.make_partitions

    def partitions(*args, **kwargs):
        res = orig(*args, **kwargs)
        res.partitions = [p[: len(p) - len(p) // 2] for p in res.partitions]
        res.graph_ids = [g[: len(g) - len(g) // 2] for g in res.graph_ids]
        return res

    mining.make_partitions = partitions


def answer_altered():
    """The largest support of every level comes off the wire one low."""
    import numpy as np

    from repro.core import level_step

    orig = level_step.unpack_wire

    def unpack(*args, **kwargs):
        w = orig(*args, **kwargs)
        gsup = np.array(w.gsup)
        if gsup.size:
            gsup[int(np.argmax(gsup))] -= 1
        return dataclasses.replace(w, gsup=gsup)

    level_step.unpack_wire = unpack


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  answer_altered)}


def imported() -> str:
    return f"[phases imported: {'bench.phases' in sys.modules}]"


def watch_fits(log):
    """Log the end of every ``Mirage.fit`` with :func:`imported`."""
    from repro.core import mining

    orig = mining.Mirage.fit

    def fit(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        log("fit ended")
        return out

    mining.Mirage.fit = fit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("n_graphs", type=int)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--watch-imports", action="store_true")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import control, harness

    if args.fault:
        FAULTS[args.fault]()
    log = (lambda msg: print(f"[drive] {msg}", file=sys.stderr, flush=True))
    if args.watch_imports:
        log = (lambda msg, say=log: say(f"{msg} {imported()}"))
        watch_fits(log)
    if args.control:
        for r in control.readings(args.cell, [1], [2, 3], root=ROOT,
                                  require_tpu=False, n_graphs=args.n_graphs):
            print(json.dumps(r), flush=True)
        return 0
    res = harness.run(args.cell, 2 ** 31 + 11, 0.1, args.trace, root=ROOT,
                      t0=t0, log=log, require_tpu=False,
                      n_graphs=args.n_graphs)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
