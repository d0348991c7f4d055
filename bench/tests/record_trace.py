"""Record the small chip trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py OUT.xplane.pb
    gzip -9 OUT.xplane.pb    # -> bench/testdata/mol64.xplane.pb.gz

Run on a TPU host.  Mines 64 molecules at minsup 30% to patterns of 3
edges through ``Mirage.fit`` on one chip, warm, under the profiler with
the benchmark's host spans, and copies the trace to ``OUT``.  Kept small
so that it can be committed under ``bench/testdata/``.
"""
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: Path) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness, system, trace
    from bench.gen import molecule

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    graphs = system.to_graphs(molecule.generate(64, 0))
    miner = system.build_miner({"n_partitions": 8},
                               {"minsup": 0.3, "max_size": 3},
                               jax.devices()[:1])
    miner.fit(graphs)
    opts = harness.profile_options()
    with tempfile.TemporaryDirectory() as tdir, system.host_spans(print):
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench:fit"):
            miner.fit(graphs)
        jax.profiler.stop_trace()
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace.find_xplane(tdir), out)
    print(f"{out}: {out.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
