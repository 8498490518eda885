import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# ^ MUST run before any jax import: jax locks the device count on first init.
# This is the ONLY module that forces 512 placeholder devices (dry-run only).

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step with optimizer
update / prefill forward / serve_step decode), abstract ShapeDtypeStruct
inputs, and full in_shardings from the resolver; compiles the SPMD program
for the production mesh; prints memory_analysis() (proves it fits) and
cost_analysis() (feeds §Roofline); parses post-optimization HLO for
collective bytes; and writes one JSON per cell under results/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all            # sweep every runnable cell
"""

import argparse
import gzip
import json
import time
import traceback

import jax

from repro.configs import shapes as shp
from repro.configs.registry import ALIASES, get_config
from repro.distributed import roofline as RL
from repro.distributed import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models.model import LM
from repro.training import lm_step, optim as O


# §Perf iteration variants: config/sharding deltas applied on top of an
# arch config. Measured against baseline via the archived HLO + roofline.
VARIANTS = {
    "baseline": {},
    "remat_dots": {"cfg": {"remat_policy": "dots"}},
    "remat_none": {"cfg": {"remat": False}},
    "kv_seqshard": {"kv_seq_shard": True},
    "tp_only": {"fsdp": False},
    "tp_remat_dots": {"fsdp": False, "cfg": {"remat_policy": "dots"}},
    "tp_kvseq": {"fsdp": False, "kv_seq_shard": True},
    "wgather": {"cfg": {"fsdp_weight_gather": True}},
    "stack_fsdp": {"fsdp_mode": "stack"},
    "stack_wgather": {"fsdp_mode": "stack",
                      "cfg": {"fsdp_weight_gather": True}},
    "stack_wg_dots": {"fsdp_mode": "stack",
                      "cfg": {"fsdp_weight_gather": True,
                              "remat_policy": "dots"}},
    "noconstr": {"cfg": {"activation_constraints": False}},
    "tp_noconstr": {"fsdp": False,
                    "cfg": {"activation_constraints": False}},
    "tp_nc_dots": {"fsdp": False,
                   "cfg": {"activation_constraints": False,
                           "remat_policy": "dots"}},
    "tp_nc_kvseq": {"fsdp": False, "kv_seq_shard": True,
                    "cfg": {"activation_constraints": False}},
    "moe_local": {"cfg": {"moe_buf_mode": "local"}},
    "moe_local_nc": {"cfg": {"moe_buf_mode": "local",
                             "activation_constraints": False}},
    "gqa_repeat": {"cfg": {"attn_gqa_mode": "repeat"}},
    "gqa_dots": {"cfg": {"attn_gqa_mode": "repeat", "remat_policy": "dots"}},
    "gqa_kvseq": {"kv_seq_shard": True,
                  "cfg": {"attn_gqa_mode": "repeat"}},
    "opt_moe": {"cfg": {"attn_gqa_mode": "repeat", "moe_buf_mode": "local"}},
    # beyond-paper sharding scheme: same 256 chips, re-meshed 64x4 so the
    # Megatron AR payload (B_local*S*d) shrinks 4x and DP grows; params must
    # fit at TP=4 (planner-checked). "a different sharding scheme" per §Perf.
    "mesh_tp4": {"mesh_shape": (64, 4), "fsdp": False,
                 "cfg": {"attn_gqa_mode": "repeat"}},
    "mesh_tp4_fsdp": {"mesh_shape": (64, 4),
                      "cfg": {"attn_gqa_mode": "repeat"}},
    "opt_decode": {"kv_seq_shard": True, "fsdp": False,
                   "cfg": {"attn_gqa_mode": "repeat"}},
    # mesh_tp4 + ZeRO-1: optimizer state sharded over data (m/v live once
    # across the fleet); params stay TP-only. Fixes tp4's HBM overshoot for
    # the price of one grad reduce-scatter + param all-gather per step.
    "mesh_tp4_z1": {"mesh_shape": (64, 4), "fsdp": False, "opt_fsdp": True,
                    "cfg": {"attn_gqa_mode": "repeat"}},
    "mesh_tp4_z1_dots": {"mesh_shape": (64, 4), "fsdp": False,
                         "opt_fsdp": True,
                         "cfg": {"attn_gqa_mode": "repeat",
                                 "remat_policy": "dots"}},
    "mesh_tp2_z1": {"mesh_shape": (128, 2), "fsdp": False, "opt_fsdp": True,
                    "cfg": {"attn_gqa_mode": "repeat"}},
    "moe_shmap": {"cfg": {"moe_buf_mode": "shard_map",
                          "attn_gqa_mode": "repeat"}},
}


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline"):
    import dataclasses
    var = VARIANTS[variant]
    cfg = get_config(arch)
    if var.get("cfg"):
        cfg = dataclasses.replace(cfg, **var["cfg"])
    cell = shp.SHAPES[shape_name]
    if var.get("mesh_shape"):
        from repro.launch.mesh import build_mesh
        shape = var["mesh_shape"]
        if multi_pod:
            shape = (2,) + shape
            mesh = build_mesh(shape, ("pod", "data", "model"))
        else:
            mesh = build_mesh(shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    constrain = SH.make_constrainer(mesh)
    lm = LM(cfg, constrain=constrain)
    pspec = lm.param_specs()
    fsdp = var.get("fsdp", True)
    fsdp_mode = var.get("fsdp_mode", "hidden")
    p_sh = SH.to_shardings(mesh, SH.param_pspecs(mesh, pspec, fsdp=fsdp,
                                                 fsdp_mode=fsdp_mode))

    if cell.kind == "train":
        optimizer = O.get(cfg.optimizer, 3e-4)
        opt_spec = jax.eval_shape(optimizer.init, pspec)
        o_fsdp = var.get("opt_fsdp", fsdp)   # ZeRO-1: shard opt state only
        o_sh = SH.to_shardings(mesh, SH.param_pspecs(
            mesh, opt_spec, fsdp=o_fsdp, fsdp_mode=fsdp_mode))
        batch_spec = SP.train_batch_specs(cfg, shape_name)
        b_sh = SH.to_shardings(mesh, SH.batch_pspec(mesh, batch_spec))
        step = lm_step.make_train_step(lm, optimizer)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     donate_argnums=(0, 1))
        args = (pspec, opt_spec, batch_spec)
    elif cell.kind == "prefill":
        batch_spec = SP.prefill_specs(cfg, shape_name)
        b_sh = SH.to_shardings(mesh, SH.batch_pspec(mesh, batch_spec))
        fwd = lm_step.make_prefill_step(lm)

        def fn_impl(params, batch):
            return fwd(params, **batch)
        fn = jax.jit(fn_impl, in_shardings=(p_sh, b_sh))
        args = (pspec, batch_spec)
    else:  # decode
        dec = SP.decode_specs(cfg, shape_name, lm)
        c_sh = SH.to_shardings(mesh, SH.cache_pspecs(
            mesh, dec["cache"], seq_shard=var.get("kv_seq_shard", False)))
        t_sh = SH.to_shardings(mesh, SH.batch_pspec(mesh, dec["tokens"]))
        step = lm_step.make_serve_step(lm)
        fn = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh),
                     donate_argnums=(1,))
        args = (pspec, dec["cache"], dec["tokens"])
    return cfg, cell, mesh, fn, args


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun",
             variant: str = "baseline") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    cfg = get_config(arch)
    runs, why = shp.applicable(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant}
    if not runs:
        rec.update(status="skipped", reason=why)
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch.replace('.', '_')}__{shape_name}__{mesh_name}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
        return rec
    t0 = time.perf_counter()
    cfg, cell, mesh, fn, args = build_cell(arch, shape_name, multi_pod,
                                           variant)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        print(mem)     # proves it fits
        print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
        hlo = compiled.as_text()
    chips = int(mesh.size)
    rl = RL.analyze(arch=arch, shape=shape_name, mesh_name=mesh_name,
                    chips=chips, cost=cost, hlo_text=hlo, cfg=cfg, cell=cell)
    rec.update(
        status="ok", chips=chips,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        flops_per_chip=rl.flops_per_chip, bytes_per_chip=rl.bytes_per_chip,
        raw_hlo_flops=rl.raw_hlo_flops, raw_hlo_bytes=rl.raw_hlo_bytes,
        coll_bytes=rl.coll_bytes, coll_by_kind=rl.coll_by_kind,
        model_flops=rl.model_flops, compute_s=rl.compute_s,
        memory_s=rl.memory_s, collective_s=rl.collective_s,
        bottleneck=rl.bottleneck, useful_ratio=rl.useful_ratio,
        step_s=rl.step_s, mfu=rl.mfu,
        memory_analysis={
            k: int(getattr(mem, k, 0)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")},
    )
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch.replace('.', '_')}__{shape_name}__{mesh_name}"
    if variant != "baseline":
        stem += f"__{variant}"
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=float)
    # archive the post-optimization HLO so perf iterations can re-analyze
    # collective schedules without recompiling
    hlo_dir = os.path.join(out_dir, "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    with gzip.open(os.path.join(hlo_dir, stem + ".txt.gz"), "wt") as f:
        f.write(hlo)
    print(rl.row())
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (assignment name)")
    ap.add_argument("--shape", default=None, choices=list(shp.SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose result JSON already exists")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    if args.all:
        ok = failed = skipped = 0
        for arch in ALIASES:
            for shape_name in shp.SHAPES:
                for mesh_name in ("single", "multi"):
                    fname = os.path.join(
                        args.out, f"{arch.replace('.', '_')}__{shape_name}"
                        f"__{mesh_name}.json")
                    if args.resume and os.path.exists(fname):
                        ok += 1
                        continue
                    try:
                        rec = run_cell(arch, shape_name, mesh_name == "multi",
                                       args.out)
                        if rec["status"] == "ok":
                            ok += 1
                        else:
                            skipped += 1
                    except Exception:
                        failed += 1
                        traceback.print_exc()
        print(f"dry-run sweep: ok={ok} skipped={skipped} failed={failed}")
        raise SystemExit(1 if failed else 0)

    rec = run_cell(args.arch, args.shape, args.mesh == "multi", args.out,
                   variant=args.variant)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("coll_by_kind", "memory_analysis")},
                     indent=1, default=float))


if __name__ == "__main__":
    main()
