"""Readings from which a cell's correctness limits are set, on the chip.

    python3 bench/calibrate.py --workload <cell> [--seeds 12]
        [--control-seeds 3] [--fault-seeds 3]

Not part of a benchmark run. In one process: the numbers the check
compares for the program on ``--seeds`` seeds (its first steps, no
window); for the control -- the reference itself in the configuration's
``control`` arithmetic put in the program's place -- on
``--control-seeds`` seeds; and for each fault planted in the reference
put in the program's place (``hts.FAULTS``) on ``--fault-seeds``
seeds. Prints one JSON object and writes it to
``bench_runs/calibrate/<cell>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "bench"]

SEED0 = 3_000_000_000


def training(cell, seeds, control_seeds, fault_seeds, control):
    from bench import session as S
    from bench.drivers import fit
    from bench.reference import envs as ref_envs
    from bench.reference import hts, nets
    faults = hts.FAULTS
    out = {"program": [], "control": [], "faults": {f: [] for f in faults}}
    run_dir = ROOT / "bench_runs" / cell.name / "calibrate"
    spec = cell.spec_dict()
    env = ref_envs.make(spec["env"]["name"], spec["env"].get("kwargs", {}),
                        cell.bench_dir)
    for i in range(seeds):
        t = time.perf_counter()
        spec, env, params0, rec, session, _ = fit.first_steps(
            cell, SEED0 + i, run_dir)
        del session
        gc.collect()
        nums = fit.check(spec, env, params0, rec, cell.bench_dir)
        out["program"].append({"seed": SEED0 + i, **nums})
        print(f"[program] seed {SEED0 + i}: {nums} "
              f"({time.perf_counter() - t:.1f}s)", file=sys.stderr,
              flush=True)
    _, apply = nets.make(S.ref_policy(spec), env.obs_shape, env.n_actions,
                         cell.bench_dir)
    hp, n_envs = S.ref_hp(spec), spec["hts"]["n_envs"]
    runs = [("control", None, control, control_seeds)] + [
        (f, f, "highest", fault_seeds) for f in faults]
    for label, fault, mode, n in runs:
        for i in range(n):
            t = time.perf_counter()
            seed = SEED0 + 100 + i
            params0 = S.host(S.make_weights(spec, env.obs_shape,
                                            env.n_actions, seed,
                                            cell.bench_dir))
            rec = hts.simulate(apply, env, params0, hp, n_envs,
                               fit.CHECK_INTERVALS, mode, fault=fault)
            nums = hts.check(apply, env, params0, hp, rec)
            (out["control"] if fault is None
             else out["faults"][fault]).append({"seed": seed, **nums})
            print(f"[{label}] seed {seed}: {nums} "
                  f"({time.perf_counter() - t:.1f}s)", file=sys.stderr,
                  flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--control", default=None,
                    help="the control's arithmetic (default: the "
                         "configuration's ``control``)")
    args = ap.parse_args(argv)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import device as device_mod
    from bench.harness import Benchmark
    cell = Benchmark(ROOT).cell(args.workload)
    devices = device_mod.require_tpu(cell.chips)
    from bench import session as S
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with S.configured(cell):
        result = training(cell, args.seeds, args.control_seeds,
                          args.fault_seeds,
                          args.control or cell.config["control"])
    result["device"] = device_mod.describe(devices)
    dest = ROOT / "bench_runs" / "calibrate"
    dest.mkdir(parents=True, exist_ok=True)
    name = cell.name + (f".{args.control}" if args.control else "")
    (dest / f"{name}.json").write_text(json.dumps(result, indent=1))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
