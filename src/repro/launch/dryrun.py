import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, prove memory fits, and extract roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json
(memory_analysis, cost_analysis, per-op collective bytes, roofline terms).

The XLA_FLAGS line above MUST run before any other import: jax locks the
device count at first backend init, and the 512 placeholder host devices
exist only for this dry-run (smoke tests and benches see 1 device).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import get_config, list_configs  # noqa: E402
from repro.core import delayed_grad, learner  # noqa: E402
from repro.launch import specs as specs_mod  # noqa: E402
from repro.launch.mesh import as_shardings, make_production_mesh  # noqa: E402
from repro.models import backbone  # noqa: E402
from repro.optim import rmsprop, adam  # noqa: E402
from repro.roofline import analysis, hlo_cost  # noqa: E402
from repro.sharding import rules  # noqa: E402

ARCH_SKIP_LIST = ()


def _mem_dict(mem) -> dict:
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes")
    return {k: getattr(mem, k) for k in keys}


def _peak_bytes(mem) -> float:
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes +
            mem.output_size_in_bytes - mem.alias_size_in_bytes)


def lower_one(arch: str, shape_name: str, mesh_name: str,
              opt_name: str = "rmsprop", extra_tag: str = "",
              overrides: dict | None = None, micro: int = 1):
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        typed = {}
        for k, v in overrides.items():
            cur = getattr(cfg, k)
            typed[k] = type(cur)(int(v) if not isinstance(cur, str) else v)
        cfg = dataclasses.replace(cfg, **typed)
    shape = specs_mod.SHAPES[shape_name]
    reason = specs_mod.skip_reason(cfg, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "skipped": reason}

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = mesh.devices.size
    abstract_params = backbone.abstract_params(cfg)
    pspecs = rules.param_pspecs(abstract_params, mesh)
    opt = rmsprop(7e-4, eps=1e-5) if opt_name == "rmsprop" else adam(1e-4)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch = specs_mod.train_batch_specs(cfg, shape)
            dg_abs = jax.eval_shape(
                lambda p: delayed_grad.init(p, opt), abstract_params)
            dg_specs = rules.dg_state_pspecs(dg_abs, pspecs, mesh)
            b_specs = rules.batch_specs(batch, mesh)
            step = learner.make_train_step(cfg, opt,
                                           n_microbatches=micro)
            out_abs = jax.eval_shape(step, dg_abs, batch)
            out_specs = (dg_specs, jax.tree.map(lambda _: P(), out_abs[1]))
            fn = jax.jit(step,
                         in_shardings=as_shardings(mesh,
                                                   (dg_specs, b_specs)),
                         out_shardings=as_shardings(mesh, out_specs),
                         donate_argnums=(0,))
            lowered = fn.lower(dg_abs, batch)
        elif shape.kind == "prefill":
            batch = specs_mod.prefill_batch_specs(cfg, shape)
            b_specs = rules.batch_specs(batch, mesh)
            step = learner.make_prefill_step(cfg, shape.seq_len)
            out_abs = jax.eval_shape(step, abstract_params, batch)
            logits_s = rules.resolve(("batch", "vocab"), out_abs[0].shape,
                                     mesh)
            value_s = rules.resolve(("batch",), out_abs[1].shape, mesh)
            cache_s = rules.cache_pspecs(out_abs[2], cfg, mesh)
            fn = jax.jit(step,
                         in_shardings=as_shardings(mesh, (pspecs, b_specs)),
                         out_shardings=as_shardings(
                             mesh, (logits_s, value_s, cache_s)))
            lowered = fn.lower(abstract_params, batch)
        else:   # decode
            token, cache_abs, pos, extras = specs_mod.decode_specs(cfg, shape)
            cache_s = rules.cache_pspecs(cache_abs, cfg, mesh)
            tok_s = rules.batch_specs({"tokens": token}, mesh)["tokens"]
            ex_s = rules.batch_specs(extras, mesh)
            step = learner.make_serve_step(cfg)
            out_abs = jax.eval_shape(step, abstract_params, token,
                                     cache_abs, pos, extras)
            logits_s = rules.resolve(("batch", "vocab"), out_abs[0].shape,
                                     mesh)
            value_s = rules.resolve(("batch",), out_abs[1].shape, mesh)
            fn = jax.jit(step,
                         in_shardings=as_shardings(
                             mesh, (pspecs, tok_s, cache_s, P(), ex_s)),
                         out_shardings=as_shardings(
                             mesh, (logits_s, value_s, cache_s)),
                         donate_argnums=(2,))
            lowered = fn.lower(abstract_params, token, cache_abs, pos,
                               extras)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # loop-aware HLO walk: XLA's cost_analysis counts while bodies once,
    # which understates scan-over-layers models by the layer count.
    hc = hlo_cost.analyze(hlo)
    coll = analysis.parse_collectives(hlo)
    mf = analysis.model_flops_for(cfg, shape.kind, shape.seq_len,
                                  shape.global_batch)
    la_cost = {"flops": hc.flops, "bytes accessed": hc.bytes,
               "transcendentals": hc.transcendentals}
    la_coll = analysis.CollectiveStats(bytes_by_op=dict(hc.collective_bytes))
    roof = analysis.build_roofline(
        arch, shape_name, mesh_name, chips, la_cost, la_coll, mf,
        _peak_bytes(mem))
    roof.note = ("loop-aware HLO cost model; bytes are an upper-bound "
                 "traffic proxy (per-op operand+output, fusion-aware)")
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "tag": extra_tag,
        "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": _mem_dict(mem),
        "peak_bytes_per_chip": _peak_bytes(mem),
        # XLA:CPU float-normalization stashes f32 copies of bf16 buffers
        # (CPU cannot execute bf16 math); the TPU pipeline keeps bf16.
        "upcast_f32_artifact_bytes": hc.upcast_f32_bytes,
        "peak_bytes_per_chip_tpu_est": _peak_bytes(mem) - hc.upcast_f32_bytes,
        "fits_16g": (_peak_bytes(mem) - hc.upcast_f32_bytes) < 16e9,
        "cost_xla_raw": {k: cost.get(k) for k in
                         ("flops", "bytes accessed", "transcendentals")
                         if k in cost},
        "cost_loop_aware": la_cost,
        "collectives": {"bytes_by_op": coll.bytes_by_op,
                        "count_by_op": coll.count_by_op,
                        "total": coll.total_bytes},
        "roofline": json.loads(roof.to_json()),
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", default="rmsprop", choices=["rmsprop", "adam"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. attn_tp_repeat=1")
    ap.add_argument("--micro", type=int, default=1,
                    help="gradient-accumulation microbatches (train)")
    ap.add_argument("--resume", action="store_true",
                    help="skip combos whose artifact already exists")
    args = ap.parse_args()
    overrides = dict(o.split("=", 1) for o in args.override)

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list_configs() if (args.all or not args.arch) else [args.arch]
    shapes = list(specs_mod.SHAPES) if (args.all or not args.shape) \
        else [args.shape]

    failures = 0
    for arch in archs:
        for shape in shapes:
            tagpart = f"__{args.tag}" if args.tag else ""
            fname = outdir / f"{arch}__{shape}__{args.mesh}{tagpart}.json"
            if args.resume and fname.exists() and \
                    "error" not in fname.read_text()[:200]:
                print(f"[RESUME-SKIP] {arch} {shape} {args.mesh}",
                      flush=True)
                continue
            t0 = time.time()
            try:
                res = lower_one(arch, shape, args.mesh, args.opt,
                                args.tag, overrides, args.micro)
            except Exception as e:
                failures += 1
                res = {"arch": arch, "shape": shape, "mesh": args.mesh,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            res["wall_s"] = round(time.time() - t0, 2)
            fname.write_text(json.dumps(res, indent=1, default=float))
            status = ("SKIP" if res.get("skipped")
                      else "FAIL" if res.get("error") else "OK")
            extra = ""
            if status == "OK":
                extra = (f" peak/chip={res['peak_bytes_per_chip_tpu_est']/1e9:.2f}GB(tpu-est)"
                         f" bottleneck={res['roofline']['bottleneck']}")
            print(f"[{status}] {arch} {shape} {args.mesh}"
                  f" ({res['wall_s']}s){extra}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
