"""Multi-tenant launcher: admit several spec files into one TenantPool
(repro.tenancy) and time-slice the device between them.

    PYTHONPATH=src python -m repro.launch.pool \
        --spec examples/specs/pool_a.json --spec examples/specs/pool_b.json
    PYTHONPATH=src python -m repro.launch.pool \
        --spec a.json --spec b.json --weight 2 --weight 1 --sequential
    PYTHONPATH=src python -m repro.launch.pool \
        --spec a.json --spec b.json --digest --check-solo

``--weight``/``--name`` repeat and align positionally with ``--spec``,
overriding each spec's ``tenancy`` block. ``--digest`` prints one
per-tenant result digest line (sha256 over final params + reward
stream + episode returns). ``--check-solo`` then re-runs every tenant
SOLO in the same process and exits nonzero unless each pooled digest
equals its solo digest — the CI smoke for the multiplexing-determinism
contract (DESIGN.md §13) in one command.
"""
from __future__ import annotations

import argparse
import hashlib
import sys
import time

import numpy as np

from repro import api
from repro.launch.cache import enable_compile_cache


def result_digest(params, rewards, episode_returns) -> str:
    """sha256 over the result's arrays, order-stable: params leaves in
    tree-flatten order, then the reward stream, then episode returns."""
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    h.update(np.ascontiguousarray(np.asarray(rewards)).tobytes())
    h.update(np.ascontiguousarray(np.asarray(episode_returns)).tobytes())
    return h.hexdigest()


def jain_index(values) -> float:
    """Jain's fairness index over per-tenant (weight-normalized)
    shares: 1.0 = perfectly proportional, 1/n = one tenant got all."""
    x = np.asarray(list(values), dtype=np.float64)
    if x.size == 0 or not x.sum():
        return float("nan")
    return float(x.sum() ** 2 / (x.size * (x ** 2).sum()))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="multi-tenant pool launcher over repro.tenancy")
    ap.add_argument("--spec", action="append", required=True,
                    metavar="FILE", help="ExperimentSpec JSON; repeat "
                    "once per tenant")
    ap.add_argument("--weight", action="append", type=int, default=None,
                    help="fair-share weight, positionally aligned with "
                    "--spec (default: each spec's tenancy.weight)")
    ap.add_argument("--name", action="append", default=None,
                    help="tenant name, positionally aligned with --spec "
                    "(default: tenancy.name or t<index>)")
    ap.add_argument("--intervals", type=int, default=None,
                    help="override every tenant's interval budget")
    ap.add_argument("--max-concurrency", type=int, default=2,
                    help="slices in flight across distinct tenants "
                    "(results are identical for every value)")
    ap.add_argument("--sequential", action="store_true",
                    help="shorthand for --max-concurrency 1")
    ap.add_argument("--digest", action="store_true",
                    help="print per-tenant result digests")
    ap.add_argument("--check-solo", action="store_true",
                    help="re-run each tenant solo and fail unless the "
                    "pooled digests match (determinism smoke)")
    args = ap.parse_args()
    enable_compile_cache()

    specs = [api.load(p) for p in args.spec]
    if args.intervals is not None:
        specs = [s.replace(intervals=args.intervals) for s in specs]
    for flag, vals in (("--weight", args.weight), ("--name", args.name)):
        if vals is not None and len(vals) != len(specs):
            ap.error(f"{flag} repeats must align with --spec: got "
                     f"{len(specs)} spec(s), {len(vals)} value(s)")

    pool = api.Session.pool(
        specs, weights=args.weight, names=args.name,
        max_concurrency=1 if args.sequential else args.max_concurrency)
    t0 = time.perf_counter()
    results = pool.run()
    wall = time.perf_counter() - t0

    total_steps = sum(r.steps for r in results.values())
    counts = pool.schedule_counts()
    weights = {name: pool._get(name).weight for name in results}
    shares = [counts[n] / weights[n] for n in results]
    print(f"[pool] {len(results)} tenants | {total_steps} steps in "
          f"{wall:.1f}s ({total_steps / max(wall, 1e-9):.0f} aggregate "
          f"SPS) | Jain fairness {jain_index(shares):.3f}")
    for name, r in results.items():
        print(f"  {name}: {r.intervals}/{r.target} intervals, "
              f"{r.steps} steps, weight {weights[name]}, "
              f"status {r.status}")

    digests = {name: result_digest(r.params, r.rewards,
                                   r.episode_returns)
               for name, r in results.items()}
    if args.digest or args.check_solo:
        for name, d in digests.items():
            print(f"  digest {name} {d}")

    if args.check_solo:
        failed = []
        for name, spec in zip(results, specs):
            r = results[name]
            solo = api.build(spec).run(r.target)
            from repro.core import evaluate
            s = evaluate.ReturnStream(spec.hts_config().n_envs)
            if solo.rewards.size:
                s.extend(solo.rewards, solo.dones)
            d = result_digest(solo.params, solo.rewards, s.returns)
            ok = d == digests[name]
            print(f"  solo   {name} {d} "
                  f"{'== pooled OK' if ok else '!= pooled MISMATCH'}")
            if not ok:
                failed.append(name)
        if failed:
            print(f"[pool] determinism check FAILED for {failed}",
                  file=sys.stderr)
            raise SystemExit(1)
        print("[pool] every tenant bit-exact to its solo run")


if __name__ == "__main__":
    main()
