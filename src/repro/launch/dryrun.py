"""Multi-pod dry-run: prove every (arch x input-shape x mesh) lowers,
compiles, and fits — and extract the roofline terms from the compiled
artifact. No arrays are ever allocated (ShapeDtypeStruct end to end).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all            # full matrix
"""
# The next two lines MUST run before ANY other import (jax locks the device
# count on first initialization).
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import functools     # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, get_config  # noqa: E402
from repro.configs.common import with_fed2            # noqa: E402
from repro.configs.shapes import INPUT_SHAPES         # noqa: E402
from repro.launch import sharding as shd              # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chips  # noqa: E402
from repro.launch.steps import (make_prefill_loss_step,          # noqa: E402
                                make_serve_step, make_train_step)
from repro.models.transformer import init_params     # noqa: E402

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-buffer bytes of every collective op in the HLO."""
    out = {c: {"bytes": 0, "count": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for coll in _COLLECTIVES:
            # match '<op>(' or '<op>-start(' as the op being executed
            if f" {coll}(" not in stripped and f" {coll}-start(" not in stripped:
                continue
            head = stripped.split(f" {coll}")[0]
            if "=" not in head:
                continue
            result = head.split("=", 1)[1]
            nbytes = 0
            for dt, dims in _SHAPE_RE.findall(result):
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _DTYPE_BYTES[dt]
            out[coll]["bytes"] += nbytes
            out[coll]["count"] += 1
            break
    return out


def applicable(arch: str, shape_name: str, *,
               swa_override: bool = False) -> tuple[bool, str]:
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.is_subquadratic \
            and not swa_override:
        return False, ("pure full-attention decoder: 524k dense KV cache "
                       "has no sub-quadratic variant in the source config "
                       "(DESIGN.md §Shape-applicability); rerun with "
                       "--swa-override for the beyond-paper SWA variant")
    return True, ""


def _spec_tree(shapes, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool,
                  fed2: bool = False, swa_override: bool = False,
                  overrides=None):
    """Lower the appropriate step for (arch, shape) on the chosen mesh."""
    import dataclasses
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch, dtype=jnp.bfloat16, **(overrides or {}))
    if swa_override and cfg.window is None and cfg.family in ("dense",
                                                              "vlm"):
        # beyond-paper opt-in: sliding-window variant for long-context
        cfg = dataclasses.replace(cfg, window=4096)
    if fed2:
        cfg = with_fed2(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)

    param_shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                  jax.random.PRNGKey(0))
    pshard = shd.param_shardings(param_shapes, cfg, mesh)
    pspecs = _spec_tree(param_shapes, pshard)

    with mesh:
        if shape.mode == "train":
            from repro.launch.analytic import param_counts
            n_par = param_counts(cfg)["total"]
            microbatches = (16 if n_par > 100e9 else
                            8 if n_par > 10e9 else
                            4 if n_par > 4e9 else 2)
            if cfg.family in ("ssm", "hybrid"):
                # SSD chunk tiles (B,H,Q,Q) dominate; smaller microbatches
                microbatches = max(microbatches, 8)
            if os.environ.get("REPRO_MICROBATCHES"):
                microbatches = int(os.environ["REPRO_MICROBATCHES"])
            step_fn, opt = make_train_step(cfg, microbatches=microbatches)
            ostate_shapes = jax.eval_shape(opt.init, param_shapes)
            zshard = shd.zero1_shardings(param_shapes, cfg, mesh)
            oshard = {"m": zshard, "v": zshard}
            ospecs = _spec_tree(ostate_shapes, oshard)
            sspec = jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
            bspecs = shd.batch_specs(cfg, shape, mesh)
            lowered = jax.jit(step_fn).lower(pspecs, ospecs, sspec, bspecs)
        elif shape.mode == "prefill":
            step_fn = make_prefill_loss_step(cfg)
            from repro.launch.analytic import param_counts
            per_group_gb = param_counts(cfg)["total"] * 2 / \
                mesh.shape["model"] / 2**30
            if per_group_gb > 12.0 or os.environ.get("REPRO_SERVE_FSDP"):
                zshard = shd.zero1_shardings(param_shapes, cfg, mesh)
                pspecs = _spec_tree(param_shapes, zshard)
            bspecs = shd.batch_specs(cfg, shape, mesh)
            lowered = jax.jit(step_fn).lower(pspecs, bspecs)
        else:  # decode
            step_fn = make_serve_step(cfg)
            # FSDP-style serving for models whose bf16 weights exceed one
            # model-group's HBM (mixtral 282GB, deepseek 472GB > 16 chips x
            # 16GB): double-shard weights over (data, model); GSPMD inserts
            # per-layer all-gathers — memory fits, collective term pays.
            from repro.launch.analytic import param_counts
            per_group_gb = param_counts(cfg)["total"] * 2 / \
                mesh.shape["model"] / 2**30
            if per_group_gb > 12.0 or os.environ.get("REPRO_SERVE_FSDP"):
                zshard = shd.zero1_shardings(param_shapes, cfg, mesh)
                pspecs = _spec_tree(param_shapes, zshard)
            cspecs = shd.cache_specs(cfg, shape, mesh)
            tok, pos = shd.decode_token_specs(cfg, shape, mesh)
            lowered = jax.jit(step_fn).lower(pspecs, cspecs, tok, pos)
    return lowered, cfg, mesh


def run_one(arch: str, shape_name: str, *, multi_pod: bool, fed2: bool,
            outdir: str, verbose: bool = True,
            swa_override: bool = False) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}_{shape_name}_{mesh_name}" + ("_fed2" if fed2 else "") \
        + ("_swa" if swa_override else "")
    ok, why = applicable(arch, shape_name, swa_override=swa_override)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "fed2": fed2, "swa_override": swa_override}
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(outdir, tag, rec)
        if verbose:
            print(f"[skip] {tag}: {why}")
        return rec
    try:
        t0 = time.time()
        lowered, cfg, mesh = build_lowered(arch, shape_name,
                                           multi_pod=multi_pod, fed2=fed2,
                                           swa_override=swa_override)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        colls = collective_bytes(compiled.as_text())
        from repro.launch.analytic import analytic_cost
        ana = analytic_cost(cfg, INPUT_SHAPES[shape_name])
        rec.update(
            status="ok",
            chips=mesh_chips(mesh),
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops=float(cost.get("flops", -1.0)),
            hlo_bytes=float(cost.get("bytes accessed", -1.0)),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
                "output_bytes": getattr(mem, "output_size_in_bytes", -1),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                      -1),
            },
            collectives=colls,
            analytic=ana,
        )
        if verbose:
            tb = rec["memory"]["temp_bytes"]
            print(f"[ok]   {tag}: lower {t_lower:.1f}s compile "
                  f"{t_compile:.1f}s flops {rec['flops']:.3e} "
                  f"temp {tb/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — record the failure, keep matrix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
    _write(outdir, tag, rec)
    return rec


def _write(outdir, tag, rec):
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"dryrun_{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--fed2", action="store_true",
                    help="apply Fed2 structure adaptation")
    ap.add_argument("--swa-override", action="store_true",
                    help="beyond-paper: sliding-window attention for dense "
                         "archs (enables long_500k)")
    ap.add_argument("--all", action="store_true",
                    help="full matrix: all archs x shapes x both meshes")
    ap.add_argument("--out", default="benchmarks/artifacts")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if (args.all or args.arch == "all") \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape == "all") \
        else [args.shape]
    meshes = [False, True] if (args.all or args.mesh == "both") \
        else [args.mesh == "multipod"]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_one(arch, shape, multi_pod=mp, fed2=args.fed2,
                              swa_override=args.swa_override,
                              outdir=args.out)
                n_fail += rec["status"] == "error"
    print(f"done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
