import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --------------------------------------------------------------------------
# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
# ShapeDtypeStruct stand-ins (no allocation), print memory/cost analysis,
# parse collective wire bytes, derive roofline terms, persist one JSON per
# cell under experiments/dryrun[/<tag>].
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
# --------------------------------------------------------------------------
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (SHAPES, cell_supported, get_config,
                                list_archs)
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model, count_params_analytic, input_specs
from repro.parallel import sharding
from repro.train import optimizer as optim
from repro.train.train_loop import make_train_step
from repro.utils import costmodel, hlo_cost, roofline
from repro import perf


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.size)
    t0 = time.monotonic()
    with sharding.use_mesh(mesh, fsdp=perf.FLAGS.fsdp):
        model = build_model(cfg)
        specs = model.param_specs()
        params = sharding.abstract_with_shardings(specs, cfg.dtype)
        ins = input_specs(cfg, shape)

        if shape.kind == "train":
            moment_dtype = ("bfloat16" if count_params_analytic(cfg) > 5e10
                            else "float32")
            opt_cfg = optim.OptConfig(moment_dtype=moment_dtype)
            opt_specs = optim.opt_state_specs(specs, opt_cfg)
            opt_abs = sharding.abstract_with_shardings(opt_specs, "float32")
            step = make_train_step(model, cfg, opt_cfg,
                                   microbatches=perf.FLAGS.microbatches)
            batch = {k: v for k, v in ins.items()}
            jitted = jax.jit(step, donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt_abs, batch)
        elif shape.kind == "prefill":
            def prefill(params, batch):
                return model.prefill(
                    params, batch["tokens"],
                    embeddings=batch.get("embeddings"))
            jitted = jax.jit(prefill)
            lowered = jitted.lower(params, ins)
        else:  # decode
            jitted = jax.jit(model.decode_step, donate_argnums=(2,))
            lowered = jitted.lower(params, ins["tokens"], ins["cache"],
                                   ins["pos"])

        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        print(f"--- {arch} x {shape_name} x "
              f"{'multi' if multi_pod else 'single'} ---")
        print(f"memory_analysis: args={mem.argument_size_in_bytes/1e9:.3f}GB "
              f"out={mem.output_size_in_bytes/1e9:.3f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.3f}GB "
              f"code={mem.generated_code_size_in_bytes/1e6:.1f}MB")
        print(f"cost_analysis (raw, while-body-once): "
              f"flops/dev={cost.get('flops', 0):.3e} "
              f"bytes/dev={cost.get('bytes accessed', 0):.3e}")
        # exact trip-count-aware extraction from the compiled module
        res = hlo_cost.analyze(compiled.as_text())
        coll = res["collective"]

        n_params = count_params_analytic(cfg)
        n_active = count_params_analytic(cfg, active_only=True)
        moment_bytes = 2 if n_params > 5e10 else 4
        bytes_dev = costmodel.hbm_bytes_per_device(
            cfg, shape, chips, model, n_params, n_active,
            moment_bytes=moment_bytes)

    dt = time.monotonic() - t0
    flops_dev = float(res["flops"]) or float(cost.get("flops", 0.0))
    rl = roofline.roofline_terms(flops_dev, bytes_dev, coll["wire_bytes"])
    mflops = roofline.model_flops(cfg, shape, n_active)
    useful = mflops / max(1.0, flops_dev * chips)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips, "status": "ok", "compile_s": round(dt, 2),
        "flops_dev": flops_dev, "bytes_dev": bytes_dev,
        "raw_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
        "roofline": rl.asdict(),
        "model_flops_total": mflops,
        "useful_flop_ratio": useful,
        "mfu_bound": roofline.mfu(mflops, rl.step_s, chips)
        if rl.step_s > 0 else 0.0,
        "params_total": count_params_analytic(cfg),
        "params_active": n_active,
        "perf_flags": perf.FLAGS.__dict__,
    }
    print(f"roofline: compute={rl.compute_s*1e3:.3f}ms "
          f"memory={rl.memory_s*1e3:.3f}ms "
          f"collective={rl.collective_s*1e3:.3f}ms -> {rl.dominant}; "
          f"useful-flop ratio={useful:.3f} mfu_bound={rec['mfu_bound']:.3f} "
          f"(compile {dt:.1f}s)")
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all")
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--set", action="append", default=[],
                   help="perf flag override, e.g. --set moe_impl=replicated")
    p.add_argument("--tag", default="baseline")
    p.add_argument("--force", action="store_true")
    p.add_argument("--out", default="experiments/dryrun")
    args = p.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        cur = getattr(perf.FLAGS, k)
        if isinstance(cur, bool):
            overrides[k] = v.lower() in ("1", "true", "yes")
        elif cur is None:
            try:
                overrides[k] = float(v)
            except ValueError:
                overrides[k] = v
        else:
            overrides[k] = type(cur)(v)
    if overrides:
        perf.set_flags(**overrides)

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    outdir = os.path.join(args.out, args.tag)
    os.makedirs(outdir, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                name = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                path = os.path.join(outdir, name + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"skip (exists): {name}")
                    continue
                try:
                    rec = lower_cell(arch, shape_name, multi)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures.append(name)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        print(f"\nFAILED cells ({len(failures)}): {failures}")
        raise SystemExit(1)
    print("\nall requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
