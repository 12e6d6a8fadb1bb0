import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402  (the device-count flag must precede every jax import)
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, ModelConfig, QuantSpec, get_config
from repro.core.twinquant import quantize_params
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.launch.roofline import Roofline
from repro.launch.sharding import batch_specs, decode_state_specs, make_shardings, param_specs
from repro.launch.train import make_train_step
from repro.models.context import MeshContext, set_mesh_context
from repro.models.registry import SHAPE_SETS, applicable_shapes, get_model, input_specs
from repro.optim import AdamW

"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN).

For every (architecture x input-shape x mesh) cell:
  jit(step).lower(**ShapeDtypeStructs).compile()
on the 16x16 (=256 chip) production mesh and the 2x16x16 (=512 chip)
multi-pod mesh, printing memory_analysis() (fits-per-device proof) and
cost_analysis() (FLOPs/bytes for §Roofline). Results land in
artifacts/dryrun/<cell>.json for launch/roofline.py + EXPERIMENTS.md.
"""


def _mesh_ctx(cfg: ModelConfig, mesh) -> MeshContext:
    dps = dp_axes(mesh)
    # FSDP policy (§Perf cell A iteration 4): ZeRO-3 param sharding forces
    # per-layer all-gathers in fwd AND bwd; for models whose bf16 params fit
    # replicated (<= ~4 GB/chip) we replicate params and ZeRO-1-shard only
    # the f32 Adam moments (see sharding.opt_state_specs).
    params_bytes = cfg.total_params() * 2
    fsdp = dps if params_bytes > 4e9 * 1 else ()
    return MeshContext(
        mesh=mesh,
        dp_axes=dps,
        tp_axis="model",
        ep_axis="model" if cfg.n_experts else None,
        fsdp_axes=fsdp,
    )


def _model_flops(cfg: ModelConfig, shape_name: str) -> float:
    spec = SHAPE_SETS[shape_name]
    n_active = cfg.active_params()
    if spec["kind"] == "train":
        return 6.0 * n_active * spec["batch"] * spec["seq"]
    if spec["kind"] == "prefill":
        return 2.0 * n_active * spec["batch"] * spec["seq"]
    return 2.0 * n_active * spec["batch"]  # decode: one token per sequence


def _shape_tree_bytes(tree) -> float:
    return sum(
        float(jnp.prod(jnp.array(l.shape)) * l.dtype.itemsize) if l.shape else l.dtype.itemsize
        for l in jax.tree.leaves(tree)
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, quant: str,
             outdir: Path, verbose: bool = True) -> dict:
    cfg = get_config(arch, quant=QuantSpec(mode=quant))
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = _mesh_ctx(cfg, mesh)
    set_mesh_context(ctx)
    model = get_model(cfg)
    chips = mesh.size
    spec = SHAPE_SETS[shape_name]
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    t0 = time.monotonic()
    params_sds = jax.eval_shape(lambda k: model.init_params(cfg, k), key_sds)
    if quant != "bf16" and spec["kind"] != "train":
        params_sds = jax.eval_shape(lambda p: quantize_params(p, cfg, cfg.quant), params_sds)
    pspecs = param_specs(cfg, params_sds, ctx)
    pshard = make_shardings(mesh, pspecs)
    batch_sds = input_specs(cfg, shape_name)
    bspecs = batch_specs(cfg, batch_sds, ctx)
    bshard = make_shardings(mesh, bspecs)

    with jax.set_mesh(mesh):
        if spec["kind"] == "train":
            opt = AdamW(moment_dtype=jnp.bfloat16 if "671b" in arch else jnp.float32)
            opt_sds = jax.eval_shape(opt.init, params_sds)
            from repro.launch.sharding import opt_state_specs

            mspecs = opt_state_specs(cfg, params_sds, pspecs, ctx)
            ospecs = type(opt_sds)(mu=mspecs, nu=mspecs, count=P())
            oshard = make_shardings(mesh, ospecs)
            step = make_train_step(cfg, opt)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_sds, opt_sds, batch_sds)
        elif spec["kind"] == "prefill":
            b = spec["batch"]
            # VLM prefill prepends n_patches stub embeddings to the sequence
            max_len = spec["seq"] + (cfg.n_patches if cfg.family == "vlm" else 0)
            state_sds = jax.eval_shape(
                lambda: model.init_decode_state(cfg, b, max_len)
            )
            sspecs = decode_state_specs(cfg, state_sds, ctx)
            sshard = make_shardings(mesh, sspecs)
            tokens = batch_sds.pop("tokens")
            tshard = bshard.pop("tokens")
            fr_key = next(iter(batch_sds), None)  # patches / frames if any

            if fr_key is None:
                def prefill_step(params, tokens, state):
                    return model.prefill(params, cfg, tokens, state)

                jitted = jax.jit(
                    prefill_step,
                    in_shardings=(pshard, tshard, sshard),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(params_sds, tokens, state_sds)
            else:
                def prefill_step(params, tokens, state, fr):
                    return model.prefill(params, cfg, tokens, state, **{fr_key: fr})

                jitted = jax.jit(
                    prefill_step,
                    in_shardings=(pshard, tshard, sshard, bshard[fr_key]),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(params_sds, tokens, state_sds, batch_sds[fr_key])
        else:  # decode
            b = spec["batch"]
            long_ctx = shape_name.startswith("long")
            state_sds = jax.eval_shape(
                lambda: model.init_decode_state(cfg, b, spec["seq"])
            )
            sspecs = decode_state_specs(cfg, state_sds, ctx, seq_shard=long_ctx)
            sshard = make_shardings(mesh, sspecs)
            tokens = batch_sds["tokens"]
            tshard = bshard["tokens"]

            def decode_step(params, state, tokens):
                return model.decode_step(params, cfg, state, tokens)

            jitted = jax.jit(
                decode_step,
                in_shardings=(pshard, sshard, tshard),
                out_shardings=None,
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_sds, state_sds, tokens)

        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    mem_fields = {}
    for f in ("temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "generated_code_size_in_bytes"):
        mem_fields[f] = getattr(mem, f, None)
    print(f"[{arch} | {shape_name} | {'multi' if multi_pod else 'single'} | {quant}] "
          f"memory_analysis: {mem_fields}")
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    cost_fields = {k: float(v) for k, v in cost.items()
                   if isinstance(v, (int, float)) and k in ("flops", "bytes accessed",
                   "bytes accessed0{}", "bytes accessed1{}", "bytes accessedout{}",
                   "optimal_seconds", "transcendentals")}
    print(f"  cost_analysis: flops={cost_fields.get('flops', 0):.3e} "
          f"bytes={cost_fields.get('bytes accessed', 0):.3e}")

    from repro.launch.hlo_cost import analyze_hlo

    hc = analyze_hlo(compiled.as_text())
    rf = Roofline(flops=hc["flops"], hbm_bytes=hc["bytes"],
                  coll_bytes=hc["coll_bytes"], chips=chips,
                  model_flops=_model_flops(cfg, shape_name))
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "quant": quant,
        "status": "ok",
        "t_lower_s": t_lower,
        "t_compile_s": t_compile,
        "memory_analysis": mem_fields,
        "param_bytes_global": _shape_tree_bytes(params_sds),
        "cost_xla_raw": cost_fields,  # XLA's scan-body-once numbers, reference
        "hlo_cost": {k: v for k, v in hc.items() if k != "coll_detail"},
        "collectives": hc["coll_detail"],
        "roofline": rf.to_dict(),
    }
    outdir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}__{quant}.json"
    (outdir / fname).write_text(json.dumps(result, indent=2))
    if verbose:
        r = result["roofline"]
        print(f"  roofline: compute={r['t_compute_s']:.4f}s memory={r['t_memory_s']:.4f}s "
              f"collective={r['t_collective_s']:.4f}s dominant={r['dominant']}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--quant", default="bf16", choices=["bf16", "w4a16", "w4a8", "w4a4"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS[:10] if args.arch == "all" else [args.arch]
    shapes = list(SHAPE_SETS) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    outdir = Path(args.out)

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        applicable = applicable_shapes(cfg)
        for shape in shapes:
            if applicable[shape] != "run":
                print(f"[{arch} | {shape}] SKIP: {applicable[shape]}")
                outdir.mkdir(parents=True, exist_ok=True)
                for mp in meshes:
                    fname = f"{arch}__{shape}__{'multi' if mp else 'single'}__{args.quant}.json"
                    (outdir / fname).write_text(json.dumps({
                        "arch": arch, "shape": shape, "quant": args.quant,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "skip", "reason": applicable[shape],
                    }, indent=2))
                continue
            for mp in meshes:
                fname = f"{arch}__{shape}__{'multi' if mp else 'single'}__{args.quant}.json"
                if args.skip_existing and (outdir / fname).exists():
                    existing = json.loads((outdir / fname).read_text())
                    if existing.get("status") == "ok":
                        print(f"[{arch} | {shape} | {fname}] exists, skipping")
                        continue
                try:
                    run_cell(arch, shape, mp, args.quant, outdir)
                except Exception as e:  # record failures; they are bugs to fix
                    traceback.print_exc()
                    failures.append((arch, shape, mp, str(e)))
                    (outdir / fname).write_text(json.dumps({
                        "arch": arch, "shape": shape, "quant": args.quant,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "fail", "error": str(e)[-2000:],
                    }, indent=2))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f[:3], f[3][:200])
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
