"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Set-up (imports, build, compile or cache
load, warm-up) counts from process start; the window then runs the
cell's traffic for ``--seconds`` with nothing compiled inside it
(the count is printed). ``--trace 1`` runs the window under the
profiler and reports the cell's per-layer metrics instead of its
end-to-end ones. After the window the outputs are compared with the
plain reference (``bench/reference``); each number compared is printed
beside its limit on the last lines of standard error, and under
``checks`` at the end of the result line, the last line of standard
output.

Exits nonzero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for. JAX's persistent compilation cache is
kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# run as a script, Python puts bench/ first on the path, where trace.py
# and session.py would shadow modules of the same name
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "bench"]


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): each number beside its limit; a number with no
    limit, or over it, is not correct."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not value <= limit:
            ok = False
    return ok, checks


def result_line(cell, rec: dict, devices, trace: bool, correct: bool,
                checks: dict) -> dict:
    from bench import device as device_mod
    from bench.harness import read_per_layer
    dev = device_mod.describe(devices)
    dev["memory_peak_bytes"] = rec["memory_peak_bytes"]
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"]}
    if trace:
        out["metrics"] = read_per_layer(cell, rec)
        tr = rec["trace"]
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": float(v), "unit": units[k]}
                          for k, v in rec["e2e"].items() if k in units}
    out["device"] = dev
    out["checks"] = checks
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_proc: float, root: Path = ROOT) -> dict:
    """Drive the cell once; returns the result line as a dict."""
    from bench import session as S
    base = root / "bench_runs" / cell.name
    ctx = {"cell": cell, "seed": seed, "seconds": seconds, "trace": trace,
           "devices": devices, "t_proc": t_proc,
           "run_dir": base / "run",
           "trace_dir": (base / "trace") if trace else None,
           "counter": S.CompileCounter()}
    with S.configured(cell):
        rec = cell.driver(ctx)
    for note in rec.get("notes", []):
        print(f"[bench] {note}", file=sys.stderr)
    print(f"[bench] compilations inside the window: "
          f"{rec['compiles_in_window']}", file=sys.stderr)
    correct, checks = judge(rec["numbers"], cell.limits.get("limits", {}))
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[check] correct = {correct}", file=sys.stderr, flush=True)
    return result_line(cell, rec, devices, trace, correct, checks)


def main(argv=None) -> int:
    args = _args(argv)
    # the compile cache lives inside the checkout, at a fixed path
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import session as S
    from_start = S.process_start()
    from bench.harness import Benchmark
    cell = Benchmark(ROOT).cell(args.workload)
    from bench import device as device_mod
    devices = device_mod.require_tpu(cell.chips)
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, from_start)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
