import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization). This module is the ONLY place the 512
# placeholder devices are forced — tests and benches see the real device.

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

For each cell the appropriate step (train_step / prefill / decode) is jitted
with full production shardings, lowered against ShapeDtypeStruct inputs (no
allocation), compiled for the forced 512-device host platform, and analyzed:

  * memory_analysis()  -> proves per-device residency fits a v5e,
  * cost_analysis()    -> per-partition FLOPs/bytes for §Roofline,
  * as_text()          -> collective schedule (parsed by launch.roofline).

Results append to a resumable JSON (--out), one record per cell x variant.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b \
        --shape train_4k --mesh multi --variant ep
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import (ARCH_IDS, SHAPES, applicable_shapes, get_config)
from repro.distributed.sharding import make_sharding_plan
from repro.launch import roofline as rl
from repro.launch.mesh import (PRODUCTION_DEVICE_KIND,
                               make_production_mesh)
from repro.models import build_model
from repro.models import layers as L
from repro.train import serve_step as ss
from repro.train import train_step as ts


def _batch_shardings(model, plan, shape):
    specs = model.input_specs(shape)
    axes = model.input_axes(shape)
    return plan.tree_shardings(axes, specs), specs


def build_cell(arch: str, shape_name: str, mesh, variant: str = "baseline",
               remat: Optional[str] = None, depth_groups: Optional[int] = None):
    """Lower + compile one cell; returns (compiled, cfg, shape).

    depth_groups builds a reduced-depth clone (same widths, same pattern,
    fewer scan groups) — used for the cost extrapolation that corrects XLA's
    count-while-loops-once cost analysis (see launch.roofline).
    """
    import dataclasses

    cfg = get_config(arch)
    # variant grammar: '+'-separated tokens, e.g. "blocked+rematfull+ep"
    tokens = set(variant.split("+")) if variant else {"baseline"}
    if "blocked" in tokens:
        cfg = dataclasses.replace(cfg, attention_impl="blocked")
    if "rematfull" in tokens:
        cfg = dataclasses.replace(cfg, remat="full")
    if "rematnone" in tokens:
        cfg = dataclasses.replace(cfg, remat="none")
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if depth_groups is not None:
        cfg = dataclasses.replace(
            cfg, n_layers=depth_groups * len(cfg.pattern),
            encoder_layers=(depth_groups if cfg.encoder_layers else 0),
            scan_unroll=True)   # unrolled -> cost analysis sees every layer
    shape = SHAPES[shape_name]
    plan = make_sharding_plan(cfg, mesh, shape, ep=("ep" in tokens),
                              fsdp=("nofsdp" not in tokens),
                              seq_parallel=("seqpar" in tokens),
                              moe_weight_stationary=("wstat" in tokens))
    model = build_model(cfg)
    batch_sh, batch_specs = _batch_shardings(model, plan, shape)

    if shape.kind == "train":
        mb = 1
        for t in tokens:
            if t.startswith("mb"):
                mb = int(t[2:])
        tcfg = ts.TrainConfig(microbatches=mb)
        step = ts.make_train_step(model, cfg, tcfg, plan)
        state_sh = plan.tree_shardings(ts.state_axes(model),
                                       ts.state_shapes(model))
        if "zero1" in tokens:
            # ZeRO-1: params replicated across the data axes (no per-layer
            # weight all-gather), optimizer moments stay FSDP-sharded — the
            # update itself reduce-scatters grads and all-gathers fresh
            # params once per step instead of per layer.
            plan_repl = make_sharding_plan(cfg, mesh, shape,
                                           ep=("ep" in tokens), fsdp=False)
            axes = ts.state_axes(model)
            shapes = ts.state_shapes(model)
            state_sh = {
                "params": plan_repl.tree_shardings(axes["params"],
                                                   shapes["params"]),
                "opt": plan.tree_shardings(axes["opt"], shapes["opt"]),
                "step": plan.sharding_for((), ()),
            }
        state_specs = ts.state_shapes(model)
        metrics_sh = jax.tree.map(
            lambda _: plan.sharding_for((), ()),
            {"loss": 0, "ce": 0, "load_balance": 0, "dropped_frac": 0,
             "lr": 0, "grad_norm": 0})
        jitted = jax.jit(step,
                         in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, metrics_sh),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_specs, batch_specs)
    elif shape.kind == "prefill":
        step = ss.make_prefill_step(model, cfg, plan)
        p_axes = L.axes_tree(model.specs)
        p_specs = L.shapes_tree(model.specs)
        params_sh = plan.tree_shardings(p_axes, p_specs)
        jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                         out_shardings=None)
        lowered = jitted.lower(p_specs, batch_specs)
    else:  # decode
        step = ss.make_decode_step(model, cfg, plan)
        p_axes = L.axes_tree(model.specs)
        p_specs = L.shapes_tree(model.specs)
        params_sh = plan.tree_shardings(p_axes, p_specs)
        out_sh = (plan.sharding_for(("act_batch", None), None),
                  batch_sh["caches"])
        jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                         out_shardings=out_sh,
                         donate_argnums=(1,))
        lowered = jitted.lower(p_specs, batch_specs)
    compiled = lowered.compile()
    return compiled, cfg, shape


def run_cell(arch: str, shape_name: str, mesh_name: str,
             variant: str = "baseline", remat: Optional[str] = None,
             verbose: bool = True) -> Dict:
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    t0 = time.time()
    with mesh:
        # 1. full-depth compile: proves the production cell compiles, and
        #    gives memory_analysis + the per-iteration collective schedule.
        compiled, cfg, shape = build_cell(arch, shape_name, mesh, variant,
                                          remat)
        # 2+3. reduced-depth clones (2 and 4 scan groups) for depth-linear
        #      cost extrapolation (XLA counts while-loop bodies once).
        g_full = cfg.n_groups
        if g_full > 1:
            g2 = min(2, g_full)
            g4 = min(4, g_full)
            if g4 == g2:
                g2 = 1
            c2, cfg2, _ = build_cell(arch, shape_name, mesh, variant, remat,
                                     depth_groups=g2)
            c4, cfg4, _ = build_cell(arch, shape_name, mesh, variant, remat,
                                     depth_groups=g4)
            costs = rl.extrapolate_costs(
                rl.extract_costs(c2, mesh.devices.size),
                rl.extract_costs(c4, mesh.devices.size),
                g2, g4, g_full)
        else:
            costs = rl.extract_costs(compiled, mesh.devices.size)
    roof = rl.analyze(compiled, cfg, shape, mesh_name, mesh.devices.size,
                      PRODUCTION_DEVICE_KIND, variant, costs=costs,
                      memory_compiled=compiled)
    record = roof.to_json()
    record["compile_seconds"] = round(time.time() - t0, 2)
    record["status"] = "ok"
    if verbose:
        print(roof.summary())
        print(f"    memory: {roof.memory_stats} "
              f"(compile {record['compile_seconds']}s)")
        print(f"    collectives: "
              f"{ {k: v['count'] for k, v in roof.collectives.items()} }")
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--all", action="store_true",
                    help="every (arch x applicable shape)")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already recorded ok in --out")
    args = ap.parse_args()

    cells = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shp in applicable_shapes(cfg):
                for m in meshes:
                    cells.append((arch, shp, m))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        for m in meshes:
            cells.append((args.arch, args.shape, m))

    results = []
    done = set()
    if args.resume and os.path.exists(args.out):
        results = json.load(open(args.out))
        done = {(r["arch"], r["shape"], r["mesh"], r.get("variant"))
                for r in results if r.get("status") == "ok"}

    print(f"dry-run: {len(cells)} cells, variant={args.variant}")
    failures = 0
    for arch, shp, m in cells:
        key = (arch, shp, m, args.variant)
        if key in done:
            print(f"skip (resume): {key}")
            continue
        try:
            rec = run_cell(arch, shp, m, args.variant, args.remat)
        except Exception as e:  # noqa: BLE001
            failures += 1
            rec = {"arch": arch, "shape": shp, "mesh": m,
                   "variant": args.variant, "status": "fail",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-3000:]}
            print(f"FAIL {arch} {shp} {m}: {type(e).__name__}: {e}")
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"done: {ok} ok / {failures} failed -> {args.out}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
