"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,tab1]
    PYTHONPATH=src python -m benchmarks.run --runtime host,mesh,sharded
    PYTHONPATH=src python -m benchmarks.run --runtime mesh \
        --append-sps BENCH_sps.json        # CI smoke: append a JSON line
    PYTHONPATH=src python -m benchmarks.run --runtime host,mesh,sharded \
        --ckpt-dir bench_ckpt --resume     # restartable long sweep

Prints ``name,value,unit`` CSV rows per benchmark. ``--runtime`` runs the
registry SPS sweep (benchmarks/engine_sps.py) for the named engine
runtimes instead of the paper tables; ``--env-backend host,device`` adds
the device-resident env axis (rows keyed ``engine_sps_<rt>_device``).
With ``--ckpt-dir`` the sweep records each completed runtime x backend
cell in ``<dir>/sweep_progress.json`` after it finishes; ``--resume``
replays recorded rows instead of re-timing them, so a preempted
multi-hour sweep restarts where it died.
"""
import argparse
import json
import os
import platform
import sys
import time
import traceback


def host_fingerprint() -> str:
    """Coarse hardware identity stamped into --append-sps records.
    benchmarks.check_sps only compares SPS between records with equal
    fingerprints: a CI runner regressing against a dev-machine baseline
    would measure hardware, not code."""
    return f"{sys.platform}-{platform.machine()}-{os.cpu_count()}cpu"

MODULES = [
    "fig3_runtime_model",
    "fig4_speedup",
    "fig4_sps_scaling",
    "fig5_curves",
    "tab1_final_time",
    "tab2_required_time",
    "tab3_multiagent",
    "tab4_actor_ablation",
    "tab5_sync_interval",
    "tabA1_correction",
    "tabA2_impl_sps",       # (engine_sps backs it; full sweep via --runtime)
    "staleness_sweep",      # throughput-vs-staleness frontier (K sweep)
    "roofline_table",
]


def _progress_path(args) -> str:
    return os.path.join(args.ckpt_dir, "sweep_progress.json")


def _load_progress(args) -> dict:
    if not (args.ckpt_dir and args.resume):
        return {}
    try:
        with open(_progress_path(args)) as f:
            saved = json.load(f)
    except (OSError, ValueError):
        return {}
    # completed runtimes are only reusable if the sweep shape matches
    if (saved.get("intervals") != args.intervals
            or saved.get("staleness", 1) != args.staleness
            or saved.get("n_replicas", "1") != args.n_replicas):
        return {}
    return saved.get("done", {})


def _save_progress(args, done: dict) -> None:
    os.makedirs(args.ckpt_dir, exist_ok=True)
    tmp = _progress_path(args) + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"intervals": args.intervals,
                   "staleness": args.staleness,
                   "n_replicas": args.n_replicas, "done": done}, f,
                  indent=1)
    os.replace(tmp, _progress_path(args))


def _sweep_progress(rt_name: str, m: dict) -> None:
    """Session on_interval observer for the sweep's warmup runs: a
    stderr marker that each runtime's warmup actually produced data
    (live per interval on the host runtime; one post-program burst on
    the fused ones). The timed run carries no observer —
    engine_sps.run."""
    if m["interval"] % 4 == 0:
        print(f"# {rt_name} warmup interval {m['interval']} "
              f"reward/step {float(m['rewards'].mean()):+.3f}",
              file=sys.stderr, flush=True)


def _run_runtime_sweep(args) -> None:
    from benchmarks import engine_sps
    names = args.runtime.split(",")
    replicas = [int(r) for r in args.n_replicas.split(",")]
    t0 = time.time()
    failed = 0
    rows_by_nr = {nr: [] for nr in replicas}
    restored_by_nr = {nr: [] for nr in replicas}
    done = _load_progress(args)
    print("name,value,unit")
    backends = args.env_backend.split(",")
    # one sweep cell per runtime x env_backend x n_replicas, isolated
    # like the tables; cells are named like their sps keys ("mesh",
    # "mesh_device", "sharded_r2") so checkpoints and check_sps's
    # restored-row staleness test agree
    cells = [(rt, be, nr) for rt in names for be in backends
             for nr in replicas]
    for rt_name, backend, nr in cells:
        cell = engine_sps.sweep_key(rt_name, backend,
                                    nr)[len("engine_sps_"):]
        if cell in done:           # resumed: replay the recorded rows
            sub = [tuple(row) for row in done[cell]]
            restored_by_nr[nr].append(cell)
            print(f"# runtime {cell} restored from checkpoint",
                  file=sys.stderr, flush=True)
        else:
            try:
                sub = engine_sps.run(runtimes=[rt_name],
                                     intervals=args.intervals,
                                     staleness=args.staleness,
                                     progress=_sweep_progress,
                                     env_backends=(backend,),
                                     n_replicas=nr)
            except Exception:
                failed += 1
                print(f"# runtime {cell} FAILED:\n"
                      f"{traceback.format_exc()}",
                      file=sys.stderr, flush=True)
                continue
            if args.ckpt_dir:
                done[cell] = sub
                _save_progress(args, done)
        rows_by_nr[nr].extend(sub)
        for name, value, unit in sub:
            print(f"{name},{value:.6g},{unit}", flush=True)
    if args.append_sps:
        # one record PER replica count: the workload fingerprint of a
        # multi-replica sweep includes its batch block, and check_sps
        # only compares records with equal configs — so replica rows
        # can never gate (or be gated by) single-replica baselines
        with open(args.append_sps, "a") as f:
            for nr in replicas:
                rows = rows_by_nr[nr]
                if not rows:
                    continue
                record = {
                    "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime()),
                    "intervals": args.intervals,
                    "host": host_fingerprint(),
                    "config": engine_sps.config_fingerprint(
                        staleness=args.staleness, n_replicas=nr),
                    "wall_s": round(time.time() - t0, 2),
                    "sps": {name: round(value, 2)
                            for name, value, _ in rows},
                }
                if restored_by_nr[nr]:
                    # replayed rows carry an older measurement's numbers
                    # — flag them so the bench trajectory isn't polluted
                    record["restored_runtimes"] = restored_by_nr[nr]
                f.write(json.dumps(record) + "\n")
        print(f"# appended to {args.append_sps}", file=sys.stderr,
              flush=True)
    if failed:
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substring filters")
    ap.add_argument("--runtime", default=None,
                    help="comma-separated engine runtime names "
                         "(host,mesh,sharded,sync,async): run the registry "
                         "SPS sweep instead of the paper tables")
    ap.add_argument("--intervals", type=int, default=12,
                    help="intervals per timed run for --runtime")
    ap.add_argument("--staleness", type=int, default=1,
                    help="HTSConfig.staleness for the --runtime sweep "
                         "(host/mesh/sharded); the sync/async baselines "
                         "refuse staleness != 1 — drop them from "
                         "--runtime when sweeping K")
    ap.add_argument("--env-backend", default="host",
                    help="comma-separated env backends for the --runtime "
                         "sweep (host,device): 'host' rows keep their "
                         "historical engine_sps_<rt> keys, 'device' rows "
                         "are keyed engine_sps_<rt>_device. Only envs "
                         "with device ports (catch, gridmaze) support "
                         "'device'")
    ap.add_argument("--n-replicas", default="1",
                    help="comma-separated replica counts for the "
                         "--runtime sweep (batch.n_replicas axis): "
                         "counts != 1 write rows keyed "
                         "engine_sps_<rt>_r<N> in their OWN --append-sps "
                         "record (the replica count is part of the "
                         "config fingerprint). Geometry-aware runtimes "
                         "only (host,mesh,sharded); sharded needs that "
                         "many visible devices")
    ap.add_argument("--append-sps", default=None, metavar="FILE",
                    help="with --runtime: append the sweep as a JSON line "
                         "to FILE (e.g. BENCH_sps.json)")
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="with --runtime: record per-runtime results in "
                         "DIR/sweep_progress.json as they complete")
    ap.add_argument("--resume", action="store_true",
                    help="with --ckpt-dir: skip runtimes already recorded "
                         "(restartable long sweeps)")
    args = ap.parse_args()
    if args.runtime and args.only:
        ap.error("--only filters the paper tables; it does not combine "
                 "with --runtime (the registry sweep)")
    if args.append_sps and not args.runtime:
        ap.error("--append-sps requires --runtime")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")
    if args.ckpt_dir and not args.runtime:
        ap.error("--ckpt-dir applies to the --runtime sweep")
    if args.env_backend != "host" and not args.runtime:
        ap.error("--env-backend applies to the --runtime sweep")
    if args.n_replicas != "1" and not args.runtime:
        ap.error("--n-replicas applies to the --runtime sweep")

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    if args.runtime:
        _run_runtime_sweep(args)
        return

    filters = args.only.split(",") if args.only else None
    print("name,value,unit")
    failed = 0
    for mod_name in MODULES:
        if filters and not any(f in mod_name for f in filters):
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["run"])
            for name, value, unit in mod.run():
                print(f"{name},{value:.6g},{unit}", flush=True)
            print(f"# {mod_name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:
            failed += 1
            print(f"# {mod_name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
