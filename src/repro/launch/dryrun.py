import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and extract the roofline inputs (deliverables e and g).

The two lines above run before ANY other import — jax locks the device count
at first init, and the dry-run needs 512 placeholder host devices to build
the (2, 16, 16) pod mesh.  Nothing here allocates full-size arrays: inputs
are ShapeDtypeStructs, and compilation is the proof that the distribution
config is coherent (sharding mismatches, unsupported collectives and
compile-time OOM all fail here).

Per cell this records into ``experiments/dryrun/<cell>.json``:
  * per-device memory breakdown (argument/output/temp/code bytes),
  * cost_analysis flops + bytes accessed (per-device, post-SPMD),
  * collective op bytes parsed from the optimized HLO (launch/hlo.py),
  * MODEL_FLOPS = 6·N_active·D (or 2· for inference) and useful-flops ratio,
  * lower/compile wall times.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out experiments/dryrun
"""
import argparse
import functools
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import LM_SHAPES
from repro.launch import specs as specs_mod
from repro.launch.hlo import HW, analyze_module, roofline_terms
from repro.launch.mesh import make_production_mesh, param_pspecs, sharding_rules
from repro.launch.steps import (
    make_decode_step, make_prefill_step, make_train_step, optimizer_pspecs,
)
from repro.models import lm, registry
from repro.nn import module as nnmod
from repro.nn.pcontext import logical_sharding
from repro.optim.adamw import AdamWConfig, adamw_init

__all__ = ["lower_cell", "run_cell", "main"]


def _sh(mesh, pspec_tree):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p), pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               mesh=None, smoke: bool = False, accum: Optional[int] = None,
               odin_mode: Optional[str] = None, remat: Optional[str] = None,
               kv_dtype: Optional[str] = None,
               rules: Optional[Dict] = None, donate: bool = True):
    """Lower one cell.  Returns (lowered, meta dict)."""
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    info = specs_mod.input_specs(arch, shape_name, smoke=smoke, accum=accum,
                                 kv_dtype=kv_dtype)
    cfg, shape = info["cfg"], info["shape"]
    if odin_mode is not None:
        cfg = cfg.with_overrides(odin_mode=odin_mode)
    if remat is not None:
        cfg = cfg.with_overrides(remat=remat)
    meta_kv = cfg.kv_dtype
    kind = info["kind"]
    rules = rules if rules is not None else sharding_rules(mesh, kind)

    spec_tree = lm.param_spec(cfg)
    aparams = nnmod.abstract(spec_tree)
    p_ps = param_pspecs(spec_tree, rules, mesh)
    param_sh = _sh(mesh, p_ps)
    n_params = nnmod.count_params(spec_tree)

    meta = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": dict(mesh.shape), "accum": info["accum"], "params": n_params,
        "smoke": smoke, "odin_mode": cfg.odin_mode, "remat": cfg.remat,
        "kv_dtype": cfg.kv_dtype,
    }

    with mesh, logical_sharding(mesh, rules):
        if kind == "train":
            opt_cfg = AdamWConfig()
            aopt = jax.eval_shape(functools.partial(adamw_init, cfg=opt_cfg), aparams)
            opt_ps = optimizer_pspecs(p_ps, opt_cfg)
            opt_sh = _sh(mesh, opt_ps)
            batch_sh = _sh(mesh, specs_mod.batch_pspecs(cfg, shape, mesh, info["accum"]))
            acc_dt = jnp.dtype(specs_mod.DRYRUN_ACCUM_DTYPE.get(arch, "float32")) \
                if not smoke else jnp.float32
            step = make_train_step(cfg, opt_cfg, accum=info["accum"],
                                   grad_shardings=param_sh, accum_dtype=acc_dt)
            meta["accum_dtype"] = str(acc_dt)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, None),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(aparams, aopt, info["batch"])
            tokens = shape.global_batch * shape.seq_len
            meta["model_flops"] = lm.model_flops(cfg, tokens, train=True)
        elif kind == "prefill":
            batch_sh = _sh(mesh, specs_mod.batch_pspecs(cfg, shape, mesh, 1))
            step = make_prefill_step(cfg, max_len=shape.seq_len)
            caches_tpl = specs_mod.abstract_caches(cfg, shape.global_batch, shape.seq_len)
            cache_sh = _sh(mesh, specs_mod.cache_pspecs(cfg, caches_tpl, mesh))
            fsdp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
            b_ax = fsdp if shape.global_batch % _ax(mesh, fsdp) == 0 else None
            v_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
            # last-position logits: [B, V] or [B, K, V] for multi-codebook
            logits_ps = (P(b_ax, None, v_ax) if cfg.n_codebooks > 1
                         else P(b_ax, v_ax))
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, batch_sh),
                out_shardings=(NamedSharding(mesh, logits_ps), cache_sh),
            )
            lowered = jitted.lower(aparams, info["batch"])
            tokens = shape.global_batch * shape.seq_len
            meta["model_flops"] = lm.model_flops(cfg, tokens, train=False)
        else:  # decode
            caches_tpl = info["caches"]
            cache_sh = _sh(mesh, specs_mod.cache_pspecs(cfg, caches_tpl, mesh))
            fsdp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
            B = shape.global_batch
            tok_ps = P(fsdp if B % _ax(mesh, fsdp) == 0 else None,
                       *([None] * (len(info["tokens"].shape) - 1)))
            tok_sh = NamedSharding(mesh, tok_ps)
            step = make_decode_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(param_sh, cache_sh, tok_sh),
                out_shardings=(tok_sh, cache_sh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(aparams, caches_tpl, info["tokens"])
            meta["model_flops"] = lm.model_flops(cfg, B, train=False)
    return lowered, meta


def _ax(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False, mesh=None,
             smoke: bool = False, accum: Optional[int] = None,
             odin_mode: Optional[str] = None, remat: Optional[str] = None,
             kv_dtype: Optional[str] = None,
             rules: Optional[Dict] = None, hw: HW = HW()) -> Dict:
    """Lower + compile + analyze one cell; returns the JSON-able record."""
    t0 = time.time()
    try:
        lowered, meta = lower_cell(
            arch, shape_name, multi_pod=multi_pod, mesh=mesh, smoke=smoke,
            accum=accum, odin_mode=odin_mode, remat=remat, kv_dtype=kv_dtype,
            rules=rules,
        )
    except Exception as e:  # a lowering failure is a bug — record it loudly
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "LOWER_FAILED", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}
    t1 = time.time()
    try:
        compiled = lowered.compile()
    except Exception as e:
        return {**meta, "multi_pod": multi_pod, "status": "COMPILE_FAILED",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}
    t2 = time.time()

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
    }
    mem["total_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                          + mem["temp_bytes"] - mem["alias_bytes"])
    ca = compiled.cost_analysis() or {}
    cost = {"xla_flops_once": float(ca.get("flops", -1.0)),
            "xla_bytes_once": float(ca.get("bytes accessed", -1.0))}

    # trip-count-aware structural analysis (launch/hlo.py) — XLA's own
    # cost_analysis counts while bodies once, useless under scan-over-layers.
    costs = analyze_module(compiled.as_text())
    cost.update({"flops": costs.flops, "bytes_accessed": costs.memory_bytes,
                 "n_whiles": costs.n_whiles,
                 "n_unknown_trip": costs.n_unknown_trip})
    coll = dict(costs.collectives)
    coll["total"] = costs.collective_total
    coll["wire_total"] = costs.collective_wire

    n_dev = int(jax.tree.reduce(lambda a, b: a * b, list(meta["mesh"].values()), 1))
    # analyzer numbers are per-partition (post-SPMD) ⇒ per-chip roofline;
    # collective term uses ring-model wire bytes (all-reduce ≈ 2× payload).
    terms = roofline_terms(costs.flops, costs.memory_bytes, costs.collective_wire, hw)
    model_flops_per_dev = meta["model_flops"] / n_dev
    terms["useful_flops_ratio"] = (
        model_flops_per_dev / costs.flops if costs.flops > 0 else -1.0
    )
    terms["mfu_upper_bound"] = (
        model_flops_per_dev / hw.peak_flops / terms["step_time_lb_s"]
        if terms["step_time_lb_s"] > 0 else -1.0
    )

    rec = {**meta, "multi_pod": multi_pod, "status": "OK",
           "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
           "n_devices": n_dev, "memory": mem, "cost": cost,
           "collectives": coll, "roofline": terms,
           "fits_hbm": mem["total_bytes"] <= hw.hbm_bytes}
    return rec


def cell_id(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=registry.ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(LM_SHAPES) + [None])
    ap.add_argument("--all", action="store_true", help="every runnable cell")
    ap.add_argument("--multi-pod", dest="mp", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    args = ap.parse_args()

    if args.all:
        cells = [(a, s.name) for a, s in registry.cells()]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        reason = registry.skip_reason(args.arch, args.shape)
        if reason:
            print(f"SKIP {args.arch} × {args.shape}: {reason}")
            return
        cells = [(args.arch, args.shape)]

    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mp]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mp in pods:
            cid = cell_id(arch, shape, mp)
            path = os.path.join(args.out, cid + ".json")
            if os.path.exists(path) and not args.force:
                print(f"cached  {cid}")
                continue
            rec = run_cell(arch, shape, multi_pod=mp, smoke=args.smoke)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            ok = rec["status"] == "OK"
            failures += 0 if ok else 1
            if ok:
                r = rec["roofline"]
                print(f"{rec['status']:4} {cid}: compile {rec['compile_s']}s  "
                      f"mem {rec['memory']['total_bytes']/1e9:.2f} GB/dev "
                      f"(fits={rec['fits_hbm']})  bottleneck={r['bottleneck']} "
                      f"[c={r['compute_s']:.2e} m={r['memory_s']:.2e} "
                      f"x={r['collective_s']:.2e}]s")
            else:
                print(f"FAIL {cid}: {rec['error']}")
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
