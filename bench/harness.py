"""One run of one benchmark cell.

A run builds the cell's DB from its configuration and the run's seed,
warms the miner up with one whole fit (every program the job uses is
compiled or loaded from the persistent cache then), and measures:

* ``--trace 0``: fits of the same DB back to back; the window closes at
  the end of the fit during which ``seconds`` have elapsed.  The
  end-to-end metrics come from it.
* ``--trace 1``: one more fit under the profiler, with the program's
  host phases in spans.  The per-layer metrics come from it, from the
  benchmark's own spans and the chip's ops (``bench/trace.py``) and
  from the program's own spans, counters and device scopes
  (``bench/phases.py``, loaded once the fit is over).

Once the measuring is done and the device memory has been read, the
plain reference mines the same DB, and every fit's frequent set and
supports are compared with it.  Every metric is read by its own module
under ``bench/metrics/`` from the :class:`RunInputs` of the run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import shutil
import tempfile
import time
import traceback
from pathlib import Path
from typing import TYPE_CHECKING, Callable, Optional

from . import cells, roofline, system
from . import trace as tracing
from .gen.common import reorder
from .peaks import peaks
from .ref import miner as refminer

if TYPE_CHECKING:
    from .phases import Phases

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: the number compared with the reference, and its limit: patterns
#: missing from a fit's answer, patterns in it that are not frequent, and
#: frequent patterns with a wrong support, summed over the run's fits.
#: The answer is exact, so the limit is 0.
LIMITS = {"wrong_patterns": 0}


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or not the compiled kernel path."""


@dataclasses.dataclass
class RunInputs:
    """What a metric reader may read.  Times in seconds."""

    setup_s: float
    window_s: float                  # wall time of the measured fits
    fits: int                        # fits completed in the window
    peak_bytes: int                  # most bytes in use on one chip
    stats: list[list[dict]]          # per fit, system.level_stats
    fit_walls: list[float]           # per fit
    devices: list[int]               # the cell's chip ids
    peaks: Optional[dict]
    shapes: list = dataclasses.field(default_factory=list)
    trace: Optional[tracing.Trace] = None
    spanned: frozenset = frozenset()  # host spans put in place (traced)
    phases: Optional[Phases] = None  # the program's spans and scopes


@contextlib.contextmanager
def count_compiles():
    """Counts jit lowerings (``lowered``, one per jit cache miss) and XLA
    compile requests (``compiled``, persistent-cache hits included)
    while the block runs."""
    import jax

    counts = {"lowered": 0, "compiled": 0}

    def on_event(name, _secs, **_kw):
        if name == LOWERING:
            counts["lowered"] += 1
        elif name == BACKEND_COMPILE:
            counts["compiled"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def pick_devices(chips: int, require_tpu: bool):
    """The cell's chips and their peaks; :class:`NoDevice` where JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devs)}")
    pk = peaks(devs[0].device_kind) if require_tpu else None
    return devs[:chips], pk


def compare(ans: Optional[tuple[list[set], dict]], ref) -> dict:
    """Patterns the fit missed, patterns it reported that are not
    frequent, and frequent patterns whose support it got wrong; a fit
    that raised (``ans`` None) misses every pattern."""
    if ans is None:
        return {"missing": len(ref.supports), "extra": 0,
                "wrong_support": 0, "raised": 1}
    levels, sups = ans
    got = set().union(*levels) if levels else set()
    want = ref.supports
    return {"missing": len(want.keys() - got),
            "extra": len(got - want.keys()),
            "wrong_support": sum(1 for c in want.keys() & got
                                 if sups.get(c) != want[c]),
            "raised": 0}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def read_metrics(entries: list[dict], inputs: RunInputs,
                 required: bool = True) -> dict:
    """Every metric ``BENCHMARK.json`` lists for the cell.  One that reads
    nothing (its kernel, op or span no longer found) fails the run, or,
    not ``required`` (the harness's own tests on the CPU, whose trace
    has no TPU ops), is left out."""
    out = {}
    for m in entries:
        value = cells.metric_reader(m["name"]).read(inputs)
        if value is None:
            if required:
                raise RuntimeError(f"metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _dump(out_dir: Path, xplane: Path, tr: tracing.Trace) -> None:
    """A copy of the trace and a summary of its device ops, for reading
    a trace by hand."""
    out_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy(xplane, out_dir / xplane.name)
    summary = {"spans": tracing.top_ops(tr.spans, 40),
               "devices": {str(d): {"n_events": len(evs),
                                    "top": tracing.top_ops(evs, 60),
                                    "async": tracing.top_ops(
                                        tr.async_ops.get(d, []), 30)}
                           for d, evs in tr.devices.items()}}
    (out_dir / "summary.json").write_text(json.dumps(summary, indent=1))


def profile_options():
    """Device ops and the benchmark's own host spans (TraceMe level 1),
    without Python calls, runtime internals or HLO protos."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def make_db(cell: cells.Cell, seed: int, n_graphs: Optional[int] = None):
    """The run's DB: the configuration's library in the seed's order."""
    config = cell.config
    n = int(n_graphs or config["n_graphs"])
    library = cells.generator(config["generator"]).generate(
        n, int(config["library_seed"]), **config.get("generator_params", {}))
    return reorder(library, seed)


def reference(cell: cells.Cell, db):
    return refminer.mine(db, math.ceil(float(cell.traffic["minsup"])
                                       * len(db)))


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path, t0: float, log: Callable[[str], None],
        require_tpu: bool = True, n_graphs: Optional[int] = None,
        dump: Optional[Path] = None) -> dict:
    """One run; returns the result line's object.  ``require_tpu`` and
    ``n_graphs`` exist for the harness's own tests on the CPU."""
    cell = cells.load_cell(workload, root)
    devices, pk = pick_devices(cell.chips, require_tpu)
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices)}
    log(f"device: {device}, ids {[d.id for d in devices]}")
    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")

    config, traffic = cell.config, cell.traffic
    db = make_db(cell, seed, n_graphs)
    n = len(db)
    graphs = system.to_graphs(db)
    miner = system.build_miner(config, traffic, devices)
    backend, packed = miner.kernel_path(n)
    log(f"{cell.name}: {n} graphs, minsup {traffic['minsup']}, "
        f"{config['n_partitions']} partitions on {len(devices)} chip(s), "
        f"kernel {backend} packed={packed}")
    if require_tpu and backend != "fused":
        raise NoDevice(f"kernel path {backend!r} is not the compiled "
                       f"fused kernel")

    with count_compiles() as warm:
        t = time.perf_counter()
        try:
            for st in system.level_stats(miner.fit(graphs)):
                log(f"  level {st}")
        except Exception:
            # the window's fits will raise too, and count as answers
            # that never came
            log(f"warm-up fit raised:\n{traceback.format_exc()}")
        log(f"warm-up fit {time.perf_counter() - t:.3f}s, "
            f"{warm['lowered']} lowered, {warm['compiled']} compiled")
    setup_s = time.perf_counter() - t0
    log(f"setup: {setup_s:.3f}s")

    answers, stats, walls = [], [], []
    tr, ph, breakdown, spanned = None, None, None, frozenset()

    def one_fit() -> bool:
        t = time.perf_counter()
        try:
            r = miner.fit(graphs)
        except Exception:
            # an answer that never comes: counted, and the window ends
            log(f"fit raised:\n{traceback.format_exc()}")
            walls.append(time.perf_counter() - t)
            answers.append(None)
            return False
        walls.append(time.perf_counter() - t)
        answers.append(system.answer(r))
        stats.append(system.level_stats(r))
        return True

    if not trace:
        with count_compiles() as win:
            start = time.perf_counter()
            while one_fit() and time.perf_counter() - start < seconds:
                pass
            window_s = time.perf_counter() - start
    else:
        import jax.profiler

        opts = profile_options()
        with tempfile.TemporaryDirectory() as tdir, \
                system.host_spans(log) as placed, count_compiles() as win:
            spanned = frozenset(placed)
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench:fit"):
                    one_fit()
            finally:
                jax.profiler.stop_trace()
            window_s = walls[-1]
            xplane = tracing.find_xplane(tdir)
            tr = tracing.load(xplane)
            if dump is not None:
                _dump(dump, xplane, tr)
            # imported only now: its proto parser takes seconds to load,
            # which must land in neither the set-up nor the window
            t = time.perf_counter()
            from . import phases

            ph = phases.load(xplane)
            log(f"program phases: {len(ph.spans)} spans, scoped "
                f"{ph.scoped}, read in {time.perf_counter() - t:.2f}s")
    log(f"window: {len(walls)} fit(s) in {window_s:.3f}s, fits "
        f"{[round(w, 3) for w in walls]}, {win['lowered']} lowered and "
        f"{win['compiled']} compiled inside the window")
    peak = peak_bytes(devices)
    device["memory_peak_bytes"] = peak

    # the reference runs once the measuring is over and the program's
    # state is gone
    del miner
    gc.collect()
    t = time.perf_counter()
    ref = reference(cell, db)
    log(f"reference: {time.perf_counter() - t:.2f}s, per level "
        f"{[len(lv.frequent) for lv in ref.levels]}")

    inputs = RunInputs(
        setup_s=setup_s, window_s=window_s, fits=len(walls),
        peak_bytes=peak, stats=stats, fit_walls=walls,
        devices=[d.id for d in devices], peaks=pk, trace=tr,
        spanned=spanned, phases=ph, shapes=roofline.level_shapes(
            ref, n, int(config["n_partitions"]),
            int(config.get("miner", {}).get("max_embeddings", 32))))
    if trace:
        metrics = read_metrics(cell.per_layer, inputs, required=require_tpu)
        device["busy_s"] = (tracing.chip_mean(tr, inputs.devices,
                                              tracing.busy_ns) or 0) / 1e9
        device["window_s"] = window_s
        fit_span = [(s, s + d) for name, s, d in tr.spans if name == "fit"]
        ops0 = tr.devices.get(inputs.devices[0], [])
        breakdown = {"device_ops": tracing.top_ops(ops0),
                     "idle_gaps": tracing.idle_gaps(
                         ops0, tr.spans,
                         window=fit_span[0] if fit_span else None)}
    else:
        metrics = read_metrics(cell.end_to_end, inputs)

    diffs = [compare(ans, ref) for ans in answers]
    total = {k: sum(d[k] for d in diffs) for k in
             ("missing", "extra", "wrong_support", "raised")}
    log(f"against the reference: {total} over {len(answers)} fit(s)")
    checks = {"wrong_patterns": sum(d["missing"] + d["extra"]
                                    + d["wrong_support"] for d in diffs)}
    failed = sum(1 for d in diffs if any(d.values()))
    correct = bool(answers) and all(checks[k] <= LIMITS[k] for k in LIMITS)
    result = {"correct": correct, "attempted": len(answers),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    for k in LIMITS:
        log(f"check {k}: {checks[k]} (limit {LIMITS[k]}) over "
            f"{len(answers)} fit(s)")
    return result
