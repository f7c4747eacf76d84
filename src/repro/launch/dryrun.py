import os
# jax locks the device count at backend init, so this MUST run before the
# `import jax` below.  Append to any pre-existing XLA_FLAGS (a user's
# --xla_dump_to etc. must survive) and defer to a caller who already pinned
# the device count themselves.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512"
                               ).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The XLA_FLAGS block above MUST stay first: the dry-run needs 512 placeholder
host devices to build the production meshes.  (Smoke tests and benches import
repro normally and see 1 device — this flag is set nowhere else.)

Per cell this script:
  1. builds abstract params / optimizer state / inputs (ShapeDtypeStruct —
     nothing is allocated),
  2. derives NamedShardings from the logical-axis rules,
  3. jit(...).lower(...).compile() against the production mesh,
  4. records memory_analysis(), cost_analysis(), the collective-byte parse
     of the partitioned HLO, and the three roofline terms,
  5. writes one JSON artifact under --out.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all            # every applicable cell
  python -m repro.launch.dryrun --all --mesh multi
  ... [--profile fsdp_tp] [--attn-impl blocked] [--xent-impl chunked] [--tag x]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch, list_archs, shape_applicable
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, model_flops_6nd, parse_collective_bytes
from repro.models import model as M
from repro.models import params as pm
from repro.optim.optimizer import OptimizerConfig, opt_state_specs
from repro.train.steps import make_train_step


def _ocfg_for(cfg) -> OptimizerConfig:
    return OptimizerConfig(name=cfg.optimizer)


# ---------------------------------------------------------------------------
# Cell builders: (fn, abstract args, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_cell(cfg, shape, mesh, profile: str):
    pspecs = M.param_specs(cfg)
    params_abs = pm.abstract_params(pspecs, jnp.dtype(cfg.param_dtype))
    params_sh = sh.specs_to_shardings(mesh, pspecs, profile)
    batch_abs = M.input_specs(cfg, shape)
    batch_sh = sh.input_shardings(mesh, cfg, batch_abs)
    scalar_sh = sh.replicated(mesh)

    if shape.kind == "train":
        ocfg = _ocfg_for(cfg)
        ospecs = opt_state_specs(ocfg, pspecs)
        opt_abs = pm.abstract_params(ospecs, jnp.float32)
        opt_sh = sh.specs_to_shardings(mesh, ospecs, profile)
        step = make_train_step(cfg, ocfg)
        args = (params_abs, opt_abs, batch_abs,
                jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (params_sh, opt_sh, batch_sh, scalar_sh)
        metrics_sh = {k: sh.replicated(mesh)
                      for k in ("loss", "xent", "aux", "grad_norm", "lr")}
        out_sh = (params_sh, opt_sh, metrics_sh)
        return step, args, in_sh, out_sh

    if shape.kind == "prefill":
        cache_len = shape.seq_len

        def prefill_fn(params, batch):
            return M.prefill(cfg, params, batch, cache_len)

        cache_abs = M.abstract_cache(cfg, shape.global_batch, cache_len)
        cache_sh = sh.cache_shardings(mesh, cfg, cache_abs, shape.global_batch, profile)
        from jax.sharding import NamedSharding
        lsh = NamedSharding(mesh, sh.batch_pspec(mesh, shape.global_batch, 3))
        return prefill_fn, (params_abs, batch_abs), (params_sh, batch_sh), \
            (lsh, cache_sh)

    # decode
    cache_abs = M.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cache_sh = sh.cache_shardings(mesh, cfg, cache_abs, shape.global_batch, profile)

    def decode_fn(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch)

    from jax.sharding import NamedSharding
    lsh = NamedSharding(mesh, sh.batch_pspec(mesh, shape.global_batch, 3))
    return decode_fn, (params_abs, cache_abs, batch_abs), \
        (params_sh, cache_sh, batch_sh), (lsh, cache_sh)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def _scaled_cfg(cfg, k: int):
    """Depth-scaled copy of cfg with k structural blocks (same block shape)."""
    from repro.models.blocks import block_size
    kw = {"num_layers": block_size(cfg) * k}
    if cfg.family == "encdec":
        kw["enc_layers"] = k
    return dataclasses.replace(cfg, **kw)


def _compile_cell(cfg, shape, mesh, profile):
    fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh, profile)
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def _costs_of(compiled) -> dict:
    ca = compiled.cost_analysis()
    coll, by_type = parse_collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll), "by_type": by_type}


def run_cell(arch: str, shape_name: str, multi_pod: bool, profile: str,
             overrides: dict, out_dir: str, tag: str = "",
             exact: bool = False) -> dict:
    cfg = dataclasses.replace(get_arch(arch), **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    chips = 512 if multi_pod else 256
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "profile": profile, "overrides": overrides, "tag": tag,
                    "chips": chips}
    if not shape_applicable(cfg, shape):
        record["ok"] = False
        record["skipped"] = ("long_500k requires a sub-quadratic decode path; "
                             f"{arch} is full-attention (see DESIGN.md)")
        _write(record, out_dir)
        return record
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        from repro.models.blocks import num_blocks
        nb = num_blocks(cfg)

        # --- phase A: FULL model (rolled scans) — proves the production
        # sharding compiles; memory_analysis is trip-count-correct. ---------
        t0 = time.perf_counter()
        compiled_full = _compile_cell(cfg, shape, mesh, profile)
        record["compile_s"] = time.perf_counter() - t0
        ma = compiled_full.memory_analysis()
        record["memory_analysis"] = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        }

        # --- phase B: cost-exact FLOPs/bytes/collectives.  XLA's
        # cost_analysis counts while-loop bodies once, so either fully unroll
        # (exact=True; slow) or exploit that every scan cost is affine in the
        # block count: lower k=1 and k=2 unrolled, fit, extrapolate to nb. ---
        if exact:
            cfg_u = dataclasses.replace(cfg, unroll_blocks=True)
            t0 = time.perf_counter()
            costs = _costs_of(_compile_cell(cfg_u, shape, mesh, profile))
            record["cost_compile_s"] = time.perf_counter() - t0
            record["cost_method"] = "unrolled-exact"
            flops, bytes_accessed, coll_bytes = (costs["flops"], costs["bytes"],
                                                 costs["coll"])
            by_type = costs["by_type"]
        else:
            # quadratic fit over k in {1,2,4} blocks; validated against the
            # fully-unrolled granite-3-2b/train_4k cell: flops within 3%,
            # bytes within 8%, collectives exact (see EXPERIMENTS.md §Dry-run)
            t0 = time.perf_counter()
            ks = (1, 2, 4)
            cs = [_costs_of(_compile_cell(
                dataclasses.replace(_scaled_cfg(cfg, k), unroll_blocks=True),
                shape, mesh, profile)) for k in ks]
            record["cost_compile_s"] = time.perf_counter() - t0
            record["cost_method"] = f"quadratic-extrapolation(k=1,2,4 -> nb={nb})"

            import numpy as _np

            def _quad(vals):
                coef = _np.polyfit(_np.array(ks, float), _np.array(vals, float), 2)
                return float(max(_np.polyval(coef, nb), vals[-1]))

            flops = _quad([c["flops"] for c in cs])
            bytes_accessed = _quad([c["bytes"] for c in cs])
            coll_bytes = _quad([c["coll"] for c in cs])
            by_type = {
                op: {"bytes": _quad([c["by_type"].get(op, {"bytes": 0})["bytes"]
                                     for c in cs]),
                     "count": _quad([c["by_type"].get(op, {"count": 0})["count"]
                                     for c in cs])}
                for op in set().union(*[c["by_type"] for c in cs])}

        record["cost_analysis"] = {"flops": flops,
                                   "bytes_accessed": bytes_accessed}
        record["collectives"] = by_type
        mf = model_flops_6nd(cfg, shape)
        roof = analyze(flops, bytes_accessed, coll_bytes, mf, chips)
        record["roofline"] = roof.to_dict()
        record["ok"] = True
        args_gb = (record['memory_analysis']['argument_bytes'] or 0) / 1e9
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name} ({profile}"
              f"{'+' + tag if tag else ''}): OK  "
              f"compute={roof.compute_s*1e3:.2f}ms mem={roof.memory_s*1e3:.2f}ms "
              f"coll={roof.collective_s*1e3:.2f}ms dominant={roof.dominant} "
              f"args/dev={args_gb:.2f}GB compile={record['compile_s']:.1f}s "
              f"costs={record['cost_compile_s']:.1f}s")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record["ok"] = False
        record["error"] = f"{type(e).__name__}: {e}"
        record["trace"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAILED {record['error']}")
    _write(record, out_dir)
    return record


def _write(record: dict, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{record['tag']}" if record.get("tag") else ""
    prof = f"__{record['profile']}" if record.get("profile", "dp_tp") != "dp_tp" else ""
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}{prof}{tag}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--profile", default="dp_tp",
                    choices=["dp_tp", "fsdp_tp", "dp_tp_hd", "fsdp_tp_hd"])
    ap.add_argument("--attn-impl", default=None, choices=["naive", "blocked"])
    ap.add_argument("--xent-impl", default=None, choices=["full", "chunked"])
    ap.add_argument("--attn-block-q", type=int, default=None)
    ap.add_argument("--remat", default=None, choices=["on", "off"])
    ap.add_argument("--attn-mixed", action="store_true")
    ap.add_argument("--moe-sharded", action="store_true")
    ap.add_argument("--exact", action="store_true",
                    help="fully unroll for cost analysis (slow cross-check)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    overrides: dict = {}
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl
    if args.xent_impl:
        overrides["xent_impl"] = args.xent_impl
    if args.attn_block_q:
        overrides["attn_block_q"] = args.attn_block_q
    if args.remat:
        overrides["remat"] = args.remat == "on"
    if args.attn_mixed:
        overrides["attn_mixed"] = True
    if args.moe_sharded:
        overrides["moe_sharded_dispatch"] = True

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if not (args.all or (args.arch and args.shape)):
        ap.error("pass --arch and --shape, or --all")

    n_ok = n_fail = n_skip = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mp, args.profile, overrides, args.out,
                               args.tag, exact=args.exact)
                if rec.get("skipped"):
                    n_skip += 1
                elif rec["ok"]:
                    n_ok += 1
                else:
                    n_fail += 1
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
