"""Mining launcher: the paper's end-to-end driver.

    python -m repro.launch.mine --dataset pubchem-like --n-graphs 200 \
        --minsup 0.2 --partitions 8 --scheme 2 --reduce reduce_scatter

Anytime mining (DESIGN.md §14): ``--deadline S`` bounds the whole run's
wall clock and ``--partial-ok`` turns budget/deadline exhaustion into a
verified PARTIAL RESULT (the frequent set through the newest audited
complete level) printed with a ``[mine] PARTIAL RESULT`` marker and
exit code 0 — the JSON written by ``--out`` then carries
``"partial": true``.  ``--level-deadline S`` pins a fixed per-phase
watchdog deadline (deterministic hang detection for CI chaos runs);
``--audit-report PATH`` dumps the continuous invariant auditor's
per-level report.  A malformed input database exits 2 with a one-line
diagnosis (graph id + edge index) instead of a traceback.

``--profile DIR`` records a ``jax.profiler`` trace of the run into DIR,
with the compiled programs' HLO: the ``mirage:`` host spans and the
``mirage/`` device scopes of ``runtime/tracing.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="pubchem-like",
                    choices=["pubchem-like", "synthetic", "paper-toy"])
    ap.add_argument("--n-graphs", type=int, default=100)
    ap.add_argument("--avg-edges", type=float, default=12.0)
    ap.add_argument("--minsup", type=float, default=0.2,
                    help="fraction (0,1) or absolute count (>=1)")
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--scheme", default="2", choices=["1", "2", "density"],
                    help="partition scheme: 1 = graph count, 2 = LPT by "
                         "edges, density = snake-deal by edge density "
                         "(Aridhi et al., arXiv 1212.0017)")
    ap.add_argument("--max-size", type=int, default=None)
    ap.add_argument("--max-embeddings", type=int, default=32)
    ap.add_argument("--reduce", default=None,
                    choices=["psum", "reduce_scatter"],
                    help="shuffle collective (default: reduce_scatter "
                         "for single_sync, psum for legacy)")
    ap.add_argument("--dense-wire", action="store_true",
                    help="disable the sharded wire layout (each worker "
                         "then fetches the FULL support vector)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable overlapped host candidate generation")
    ap.add_argument("--backend", default=None,
                    choices=[None, "ref", "pallas", "interpret", "fused",
                             "fused_interpret"])
    ap.add_argument("--pipeline", default="single_sync",
                    choices=["single_sync", "device_loop", "legacy"],
                    help="single_sync: one device program + one host "
                         "sync per level (default); device_loop: the "
                         "ENTIRE run as one lax.while_loop program with "
                         "a single device->host transfer (needs "
                         "--max-size); legacy: the PR-1 two-program "
                         "driver")
    ap.add_argument("--candgen", default="host",
                    choices=["host", "device"],
                    help="candidate generation for the per-level "
                         "pipelines: host python generator (default) or "
                         "the jitted device generator (the device_loop "
                         "stepping stone)")
    ap.add_argument("--device-c-budget", type=int, default=None,
                    help="device_loop: canonical candidate budget per "
                         "loop iteration (default: auto-sized)")
    ap.add_argument("--device-raw-budget", type=int, default=None,
                    help="device_loop: structural slot budget before "
                         "canonicality (default: 4x the c-budget)")
    ap.add_argument("--device-max-states", type=int, default=64,
                    help="device canonicality machine state bound")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="device_loop: checkpoint-chunk cadence in "
                         "levels (default: no mid-run checkpoints — "
                         "exactly one transfer per run)")
    ap.add_argument("--unroll", type=int, default=0,
                    help="device_loop: >0 replaces the while_loop with "
                         "this many cond-gated body applications per "
                         "program invocation")
    ap.add_argument("--no-bucket", action="store_true",
                    help="disable shape bucketing (one XLA compile per "
                         "mining level instead of per bucket family)")
    ap.add_argument("--bucket-floors", default=None, metavar="C,S,K",
                    help="bucket family floors for the candidate axis, "
                         "survivor cap and vertex slots (default 64,32,8)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write result JSON here")
    ap.add_argument("--fault-schedule", default=None, metavar="SPEC",
                    help="chaos mode: inject a deterministic fault "
                         "schedule, e.g. 'worker_loss@2;wire_bitflip@3'"
                         " (see repro.runtime.faults); mining runs "
                         "under the recovery supervisor")
    ap.add_argument("--max-retries", type=int, default=5,
                    help="supervisor recovery-attempt budget")
    ap.add_argument("--fault-log", default=None,
                    help="write the structured fault-event log (JSONL, "
                         "one line per event, crash-safe) here; implies "
                         "supervised mining")
    ap.add_argument("--deadline", type=float, default=None,
                    help="whole-run wall-clock budget in seconds; "
                         "implies supervised mining (DESIGN.md §14)")
    ap.add_argument("--level-deadline", type=float, default=None,
                    help="fixed per-phase watchdog deadline in seconds "
                         "(default: self-calibrating EWMA policy)")
    ap.add_argument("--partial-ok", action="store_true",
                    help="on deadline/retry-budget exhaustion return a "
                         "verified PARTIAL RESULT (exit 0 + marker) "
                         "instead of raising; implies supervised mining")
    ap.add_argument("--no-audit", action="store_true",
                    help="disable the continuous invariant auditor "
                         "(device audit word + host spot checks)")
    ap.add_argument("--audit-report", default=None,
                    help="write the auditor's per-level report JSON here")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a jax.profiler trace of the run in DIR "
                         "(TensorBoard's profile plugin or xprof open "
                         "it), with the programs' HLO")
    args = ap.parse_args()

    import jax

    from repro.core.graphdb import (GraphValidationError, paper_toy_db,
                                    pubchem_like_db, random_db)
    from repro.core.mining import Mirage, MirageConfig, PartialResult
    from repro.core.supervisor import MiningSupervisor, SupervisorConfig
    from repro.runtime import faults
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.runtime.watchdog import Watchdog

    enable_compile_cache()

    if args.dataset == "paper-toy":
        graphs = paper_toy_db()
    elif args.dataset == "pubchem-like":
        graphs = pubchem_like_db(args.n_graphs, seed=args.seed,
                                 avg_edges=args.avg_edges)
    else:
        graphs = random_db(args.n_graphs, seed=args.seed)

    minsup = args.minsup if args.minsup < 1 else int(args.minsup)
    bucket_kw = {}
    if args.bucket_floors:
        c, s, k = (int(x) for x in args.bucket_floors.split(","))
        bucket_kw = dict(bucket_c_floor=c, bucket_s_floor=s,
                         bucket_k_floor=k)
    scheme = args.scheme if args.scheme == "density" else int(args.scheme)
    cfg = MirageConfig(
        minsup=minsup, n_partitions=args.partitions, scheme=scheme,
        max_size=args.max_size, max_embeddings=args.max_embeddings,
        reduce=args.reduce, backend=args.backend,
        sharded_wire=False if args.dense_wire else None,
        overlap_candgen=not args.no_overlap,
        pipeline=args.pipeline, candgen=args.candgen,
        device_c_budget=args.device_c_budget,
        device_raw_budget=args.device_raw_budget,
        device_max_states=args.device_max_states,
        device_loop_ckpt_every=args.ckpt_every,
        device_loop_unroll=args.unroll,
        checkpoint_dir=args.ckpt_dir,
        bucket_shapes=not args.no_bucket,
        audit=not args.no_audit, **bucket_kw)

    supervised = (args.fault_schedule or args.fault_log
                  or args.deadline is not None or args.partial_ok)
    if args.fault_schedule:
        schedule = faults.FaultSchedule.parse(args.fault_schedule)
        faults.install(schedule)
        print(f"[mine] chaos schedule: {schedule.describe()}")

    if args.profile:
        opts = jax.profiler.ProfileOptions()
        # the optimized HLO names each device op's mirage/ scope
        opts.enable_hlo_proto = True
        jax.profiler.start_trace(args.profile, profiler_options=opts)
    sup = miner = None
    t0 = time.perf_counter()
    try:
        if supervised:
            watchdog = None
            if args.level_deadline is not None:
                watchdog = Watchdog(run_deadline_s=args.deadline,
                                    phase_default=args.level_deadline)
            sup = MiningSupervisor(
                cfg, SupervisorConfig(
                    max_retries=args.max_retries,
                    fault_log_path=args.fault_log,
                    deadline_s=args.deadline,
                    on_exhausted="partial" if args.partial_ok
                    else "raise"),
                watchdog=watchdog)
            res = sup.mine(graphs, resume=args.resume)
        else:
            miner = Mirage(cfg)
            res = miner.fit(graphs, resume=args.resume)
            if miner.last_device_loop is not None:
                info = miner.last_device_loop
                print(f"[mine] device_loop: completed={info['completed']} "
                      f"chunks={info['chunks']} "
                      f"escalations={info['escalations']}"
                      + (f" fallback={info['fallback']}"
                         if info["fallback"] else ""))
    except GraphValidationError as exc:
        # a malformed database is an input bug, not a crash: diagnose
        # (graph id + edge index) on stderr, no traceback
        print(f"[mine] invalid database: {exc}", file=sys.stderr)
        raise SystemExit(2)
    finally:
        if args.profile:
            jax.profiler.stop_trace()
    dt = time.perf_counter() - t0

    if sup is not None and sup.events:
        print(f"[mine] recovered from {len(sup.events)} fault(s):")
        for ev in sup.events:
            print(f"  attempt {ev.attempt}: {ev.kind} at level "
                  f"{ev.level} -> {ev.action} ({ev.detail})")
    if sup is not None and sup.watchdog and sup.watchdog.trips:
        for trip in sup.watchdog.trips:
            print(f"[mine] watchdog trip: level {trip['level']} "
                  f"exceeded {trip['deadline_s']:.2f}s phase deadline "
                  f"after {trip['elapsed_s']:.2f}s")

    partial = isinstance(res, PartialResult)
    if partial:
        print(f"[mine] PARTIAL RESULT ({res.reason}): verified prefix "
              f"through level {res.last_level}, audited={res.audited}")
    dev = jax.devices()[0]
    ran = miner if sup is None else sup.last_miner   # after any ladder rung
    backend, packed = ran.kernel_path(len(graphs))
    print(f"[mine] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}  backend={backend} packed={packed}")
    print(f"[mine] |G|={len(graphs)} minsup={res.minsup} "
          f"partitions={args.partitions} scheme={args.scheme} "
          f"reduce={cfg.reduce}")
    print(f"[mine] frequent patterns: {sum(res.counts())} "
          f"(per level: {res.counts()})")
    if partial:
        print(f"[mine] wall: {dt:.2f}s")
    else:
        print(f"[mine] wall: {dt:.2f}s  overflow: {res.total_overflow}")
        for st in res.stats:
            print(f"  level {st.level}: candidates={st.n_candidates} "
                  f"frequent={st.n_frequent} {st.seconds:.2f}s "
                  f"(map {st.map_seconds:.2f}s) "
                  f"imbalance={st.imbalance:.2f}"
                  f"{' [rebalanced]' if st.rebalanced else ''}")
    if args.audit_report:
        report = (sup.audit_report if sup is not None
                  else (miner.auditor.report if miner and miner.auditor
                        else []))
        with open(args.audit_report, "w") as f:
            json.dump(report, f, indent=1)
        print(f"[mine] audit report ({len(report)} row(s)) -> "
              f"{args.audit_report}")
    if args.out:
        payload = {
            "n_graphs": len(graphs), "minsup": res.minsup,
            "counts": res.counts(), "seconds": dt,
            "levels": [[list(map(list, c)) for c in lvl]
                       for lvl in res.levels],
        }
        if partial:
            payload.update(partial=True, reason=res.reason,
                           last_level=res.last_level,
                           audited=res.audited)
        with open(args.out, "w") as f:
            json.dump(payload, f)


if __name__ == "__main__":
    main()
