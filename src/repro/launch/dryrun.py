import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count on first init, and the production mesh needs 512 placeholder devices.

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, SHAPES, SMOKE_SHAPES, \
    shape_applicable, reduced
from repro.launch.mesh import make_production_mesh, make_small_mesh
from repro.models import model as M
from repro.models import sharding
from repro.roofline.analysis import roofline_terms, model_flops
from repro.train.train_step import make_train_step


def _sds(tree, spec_tree, mesh):
    def one(leaf, spec):
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree.map(one, tree, spec_tree)


def _batch_axis(n: int, mesh) -> Any:
    dp = sharding._STATE["dp"]
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    return "dp" if n % size == 0 else None


def batch_specs(cfg, shape, kind, mesh):
    specs = M.input_specs(cfg, shape.seq_len, shape.global_batch, kind)
    ba = _batch_axis(shape.global_batch, mesh)

    def one(k, leaf):
        if leaf.ndim == 0:
            spec = P()
        else:
            spec = sharding.pspec(ba, *([None] * (leaf.ndim - 1)))
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return {k: one(k, v) for k, v in specs.items()}


def opt_pspecs(cfg, params_abs, opt_abs):
    """Optimizer-state specs mirror the param specs; Adafactor's factored
    leaves inherit truncated specs (vr: drop last dim; vc: drop 2nd-last)."""
    pspecs = sharding.param_pspecs(params_abs)
    leaves, treedef = jax.tree_util.tree_flatten(params_abs)
    spec_leaves = treedef.flatten_up_to(pspecs)

    def like_params(tree):
        return treedef.unflatten(spec_leaves)

    if cfg.optimizer == "adamw":
        m = like_params(opt_abs.m)
        v = like_params(opt_abs.v)
    else:
        m = None
        v_leaves = []
        for spec, pleaf in zip(spec_leaves, leaves):
            parts = list(spec)
            parts += [None] * (len(pleaf.shape) - len(parts))
            if len(pleaf.shape) >= 2:
                vr = P(*parts[:-1])
                vc = P(*(parts[:-2] + parts[-1:]))
                v_leaves.append((vr, vc))
            else:
                v_leaves.append((P(*parts),))
        v = treedef.unflatten(v_leaves)
    return type(opt_abs)(step=P(), m=m, v=v, comp_err=None)


def _cache_spec(path, leaf, mesh, batch):
    keys = [p.key for p in path if hasattr(p, "key")]
    name = keys[-1] if keys else ""
    ba = _batch_axis(batch, mesh)
    nd = leaf.ndim
    if name in ("self_k", "self_v", "cross_k", "cross_v"):
        # (period, B, S, KvH, Dh): flash-decode style - sequence over 'tp'
        spec = sharding.pspec(None, ba, "tp", None, None)
    elif nd == 5:   # ssm_state (period, B, H, P, N)
        spec = sharding.pspec(None, ba, "tp", None, None)
    elif nd == 4:   # conv states (period, B, 3, C)
        tp = "tp" if leaf.shape[-1] % mesh.shape[sharding._STATE["tp"]] == 0 \
            and leaf.shape[-1] >= 1024 else None
        spec = sharding.pspec(None, ba, None, tp)
    else:
        spec = sharding.pspec(*([None] * nd))
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                sharding=NamedSharding(mesh, spec))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               router: Optional[str] = None, small: bool = False,
               smoke: bool = False, unroll: bool = True,
               seq_shard: bool = False, fast_decode: bool = False,
               parallel_block: bool = False):
    """Returns (lowered, meta) for one (arch x shape x mesh) cell."""
    cfg = ARCHS[arch]
    if smoke:
        cfg = reduced(cfg)
    if router:
        cfg = cfg.with_(router=router)
    cfg = cfg.with_(scan_unroll=unroll, seq_shard=seq_shard,
                    fast_decode_math=fast_decode,
                    parallel_block=parallel_block)
    shape = (SMOKE_SHAPES if smoke else SHAPES)[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": reason}
    mesh = make_small_mesh() if small else make_production_mesh(
        multi_pod=multi_pod)
    sharding.set_mesh(mesh)
    params_abs = M.abstract_params(cfg)
    pspecs = sharding.param_pspecs(params_abs)
    params_sds = _sds(params_abs, pspecs, mesh)

    if shape.kind == "train":
        opt_init, step_fn = make_train_step(cfg)
        opt_abs = jax.eval_shape(opt_init, params_abs)
        opt_sds = _sds(opt_abs, opt_pspecs(cfg, params_abs, opt_abs), mesh)
        batch_sds = batch_specs(cfg, shape, "train", mesh)
        lowered = step_fn.lower(params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        batch_sds = batch_specs(cfg, shape, "prefill", mesh)
        fn = jax.jit(lambda p, b: M.prefill(p, cfg, b))
        lowered = fn.lower(params_sds, batch_sds)
    elif shape.kind == "decode":
        cache_abs = jax.eval_shape(
            lambda p, b: M.prefill(p, cfg, b)[0],
            params_abs,
            M.input_specs(cfg, shape.seq_len, shape.global_batch, "prefill"),
        )
        cache_sds = jax.tree_util.tree_map_with_path(
            lambda pth, l: _cache_spec(pth, l, mesh, shape.global_batch),
            cache_abs,
        )
        ba = _batch_axis(shape.global_batch, mesh)
        tok_sds = jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32,
            sharding=NamedSharding(mesh, sharding.pspec(ba, None)),
        )
        pos_sds = jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P()))
        fn = jax.jit(
            lambda p, c, t, pos: M.decode_step(p, cfg, c, t, pos),
            donate_argnums=(1,),
        )
        lowered = fn.lower(params_sds, cache_sds, tok_sds, pos_sds)
    else:
        raise ValueError(shape.kind)
    return lowered, {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "multi_pod": multi_pod, "router": cfg.router,
        "n_chips": int(np.prod(list(mesh.shape.values()))),
        "mesh": dict(mesh.shape), "cfg_shape": shape,
        "cfg": cfg,
    }


def run_cell(arch, shape_name, *, multi_pod=False, router=None, small=False,
             smoke=False, save_hlo: Optional[str] = None, unroll=True,
             seq_shard=False, fast_decode=False,
             parallel_block=False) -> Dict:
    t0 = time.time()
    try:
        lowered, meta = lower_cell(
            arch, shape_name, multi_pod=multi_pod, router=router,
            small=small, smoke=smoke, unroll=unroll, seq_shard=seq_shard,
            fast_decode=fast_decode, parallel_block=parallel_block,
        )
        if lowered is None:
            return {"arch": arch, "shape": shape_name,
                    "multi_pod": multi_pod, "ok": True, **meta}
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        tp_size = meta["mesh"].get("model", 16)
        terms = roofline_terms(cost, hlo)
        mf = model_flops(meta["cfg"], meta["cfg_shape"], meta["n_chips"])
        result = {
            "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
            "router": meta["router"], "ok": True,
            "n_chips": meta["n_chips"], "mesh": meta["mesh"],
            "kind": meta["kind"],
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_per_device_gb": round(
                    (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
                    / 2**30, 3),
            },
            "unroll": unroll, "seq_shard": seq_shard,
            "roofline": {k: v for k, v in terms.items()},
            "model_flops": mf,
            "hlo_flops_ratio": (
                mf["model_flops_per_device"]
                / max(terms["flops_per_device"], 1.0)
            ),
        }
        if save_hlo:
            os.makedirs(save_hlo, exist_ok=True)
            tag = f"{arch}__{shape_name}__{'mp' if multi_pod else 'sp'}"
            with open(os.path.join(save_hlo, tag + ".collectives.txt"),
                      "w") as f:
                for line in hlo.splitlines():
                    if any(op in line for op in (
                            "all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute")):
                        f.write(line.strip()[:400] + "\n")
        return result
    except Exception as e:
        return {
            "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
            "ok": False, "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
            "compile_s": round(time.time() - t0, 1),
        }


def all_cells():
    for arch in ARCHS:
        for shape in SHAPES:
            for mp in (False, True):
                yield arch, shape, mp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--router", default=None)
    ap.add_argument("--small", action="store_true",
                    help="2x4 CI mesh instead of production mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced arch config + tiny shapes")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell in "
                         "subprocesses and aggregate")
    ap.add_argument("--only-mesh", choices=["sp", "mp"], default=None)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--no-unroll", action="store_true",
                    help="keep lax.scan over layers (faster compile; XLA "
                         "costs the body once -> flops undercounted)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel residual stream (hillclimb)")
    ap.add_argument("--fast-decode", action="store_true",
                    help="bf16 cache reads w/ fp32 accumulation (hillclimb)")
    ap.add_argument("--parallel-block", action="store_true",
                    help="PaLM-style parallel attn+FFN block (hillclimb)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        for arch, shape, mp in all_cells():
            if args.only_mesh == "sp" and mp:
                continue
            if args.only_mesh == "mp" and not mp:
                continue
            tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", args.out]
            if mp:
                cmd.append("--multi-pod")
            if args.no_unroll:
                cmd.append("--no-unroll")
            if args.save_hlo:
                cmd += ["--save-hlo", args.save_hlo]
            print(f"[dryrun] {tag} ...", flush=True)
            subprocess.run(cmd, check=False)
        return

    res = run_cell(
        args.arch, args.shape, multi_pod=args.multi_pod, router=args.router,
        small=args.small, smoke=args.smoke, save_hlo=args.save_hlo,
        unroll=not args.no_unroll, seq_shard=args.seq_shard,
        fast_decode=args.fast_decode, parallel_block=args.parallel_block,
    )
    tag = f"{args.arch}__{args.shape}__{'mp' if args.multi_pod else 'sp'}"
    if args.router:
        tag += f"__{args.router}"
    if args.smoke or args.small:
        tag += "__smoke"
    if args.tag:
        tag += f"__{args.tag}"
    path = os.path.join(args.out, tag + ".json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1, default=str)
    print(json.dumps(
        {k: res.get(k) for k in ("arch", "shape", "multi_pod", "ok",
                                 "skipped", "error", "compile_s")},
        default=str))
    if res.get("ok") and "roofline" in res:
        r = res["roofline"]
        print(f"  terms: compute={r['t_compute_s']:.4f}s "
              f"memory={r['t_memory_s']:.4f}s "
              f"collective={r['t_collective_s']:.4f}s "
              f"dominant={r['dominant']} "
              f"roofline_frac={r['roofline_fraction']:.3f}")
        print(f"  mem/device: {res['memory']['peak_per_device_gb']} GiB; "
              f"model/HLO flops ratio: {res['hlo_flops_ratio']:.3f}")


if __name__ == "__main__":
    main()
