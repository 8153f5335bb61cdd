"""Multi-pod dry-run — deliverable (e).

For every (architecture x input-shape) cell, on the single-pod 16x16 mesh
AND the 2x16x16 multi-pod mesh:

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...,
                           donate_argnums=...).lower(*input_specs(...))
        compiled = lowered.compile()
        compiled.memory_analysis()    # proves it fits 16 GB/chip
        compiled.cost_analysis()      # FLOPs / bytes for the roofline

plus an HLO parse summing the operand bytes of every collective op
(all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute)
— cost_analysis does not report collective traffic.

Results land in artifacts/dryrun/<mesh>/<arch>__<shape>.json; the roofline
benchmark and EXPERIMENTS.md read from there.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede every other import: jax locks the device count on first init.
#   (__future__ is the only legal statement allowed above this line.)

import argparse
import dataclasses
import json
import pathlib
import re
import time
import traceback

import jax

from repro.configs import ARCH_SPECS, SHAPES, get_arch
from repro.configs.base import ArchSpec
from repro.configs.shapes import Shape
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.optim.adamw import opt_state_sharding
from repro.runtime.sharding import batch_sharding, build_rules, cache_sharding
from repro.runtime.steps import (StepConfig, make_prefill_step,
                                 make_serve_step, make_train_step)

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_COLL_RE = re.compile(
    r"=\s*(\S+?)\[?([\d,]*)\]?\{?[^=]*?\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _dtype_bytes(dt: str) -> int:
    return {"f64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
            "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
            "c64": 8, "c128": 16, "s64": 8, "u64": 8}.get(dt, 4)


def parse_collectives(hlo_text: str) -> dict[str, dict[str, float]]:
    """Sum result-shape bytes of every collective in post-SPMD HLO."""
    out: dict[str, dict[str, float]] = {}
    # result types look like:  bf16[16,4096]{1,0} all-gather(...)
    pat = re.compile(
        r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s*"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(")
    for m in pat.finditer(hlo_text):
        tuple_types, dt, dims, op = m.groups()
        nbytes = 0
        if tuple_types:                          # tuple result (async pairs)
            for t in re.finditer(r"(\w+)\[([\d,]*)\]", tuple_types):
                d, ds = t.groups()
                n = 1
                for x in ds.split(","):
                    if x:
                        n *= int(x)
                nbytes += n * _dtype_bytes(d)
        else:
            n = 1
            for x in (dims or "").split(","):
                if x:
                    n *= int(x)
            nbytes = n * _dtype_bytes(dt)
        slot = out.setdefault(op, {"count": 0, "bytes": 0.0})
        slot["count"] += 1
        slot["bytes"] += float(nbytes)
    return out


def lower_cell(spec: ArchSpec, shape: Shape, mesh, step_cfg: StepConfig):
    """Build + lower + compile one cell; returns the record dict."""
    cfg = spec.config
    rules = build_rules(cfg, mesh, sequence_shard=step_cfg.sequence_shard,
                        moe_strategy=step_cfg.moe_strategy)
    ins = S.input_specs(spec, shape, step_cfg)
    t0 = time.time()

    with mesh:
        if ins["kind"] == "train":
            psh = rules.param_sharding(ins["axes"])
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(mesh, PartitionSpec())
            state_sh = {"params": psh,
                        "opt": opt_state_sharding(psh, ins["state"]["opt"], mesh),
                        "step": rep}
            batch_sh = batch_sharding(rules, ins["batch"])
            step = make_train_step(cfg, step_cfg, rules)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(ins["state"], ins["batch"])
        elif ins["kind"] == "prefill":
            psh = rules.param_sharding(ins["axes"])
            batch_sh = batch_sharding(rules, ins["batch"])
            step = make_prefill_step(cfg, step_cfg, rules,
                                     max_len=ins["max_len"])
            cache_abs = jax.eval_shape(step, ins["params"], ins["batch"])[1]
            cache_sh = cache_sharding(rules, cache_abs, cfg)
            jitted = jax.jit(step, in_shardings=(psh, batch_sh),
                             out_shardings=(None, cache_sh))
            lowered = jitted.lower(ins["params"], ins["batch"])
        else:                                      # decode
            psh = rules.param_sharding(ins["axes"])
            cache_sh = cache_sharding(rules, ins["cache"], cfg)
            tok_sh = batch_sharding(rules, ins["tokens"])
            step = make_serve_step(cfg, step_cfg, rules)
            jitted = jax.jit(step, in_shardings=(psh, cache_sh, tok_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(ins["params"], ins["cache"], ins["tokens"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    from repro.launch import hloparse
    cost = compiled.cost_analysis()
    hlo = hloparse.analyze(compiled.as_text())

    record = {
        "arch": spec.arch_id,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": int(mesh.devices.size),
        "seconds_lower": round(t_lower, 2),
        "seconds_compile": round(t_compile, 2),
        # honest per-device numbers: while bodies multiplied by trip count
        "flops_per_device": float(hlo["dot_flops"]),
        "hbm_bytes_per_device": float(hlo["hbm_bytes"]),
        "collective_bytes_per_device": float(hlo["collective_bytes"]),
        # raw cost_analysis (loop bodies counted ONCE — reference only)
        "xla_flops_raw": float(cost.get("flops", -1.0)),
        "xla_bytes_raw": float(cost.get("bytes accessed", -1.0)),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", -1)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", -1)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", -1)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", -1)),
        },
        "collectives": hlo["collectives"],
        "params_total": spec.config.param_count(),
        "params_active": spec.config.active_param_count(),
        "step_cfg": {"n_micro": step_cfg.n_micro, "remat": step_cfg.remat,
                     "sequence_shard": step_cfg.sequence_shard,
                     "moe_strategy": step_cfg.moe_strategy},
    }
    return record


def default_step_cfg(spec: ArchSpec, shape: Shape) -> StepConfig:
    """Per-cell microbatching: keep per-device live activations bounded."""
    if shape.kind != "train":
        return StepConfig(n_micro=1, remat="none")
    # per-device batch = global / DP shards (16 single-pod, 32 multi-pod);
    # 8 microbatches keeps layer boundaries < ~100 MB for the big archs
    n_micro = 8 if shape.global_batch >= 64 else 1
    return StepConfig(n_micro=n_micro, remat="full")


def run_cells(arch_ids, shape_names, meshes, out_dir: pathlib.Path,
              step_cfg: StepConfig | None = None, tag: str = "",
              pad_heads: bool = False):
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        mdir = out_dir / mesh_name
        mdir.mkdir(parents=True, exist_ok=True)
        for aid in arch_ids:
            spec = get_arch(aid)
            for sname in shape_names:
                shape = SHAPES[sname]
                if shape not in spec.shapes():
                    print(f"SKIP  {aid} x {sname} (long-context not "
                          f"applicable; see DESIGN.md)")
                    continue
                scfg = step_cfg or default_step_cfg(spec, shape)
                run_spec = spec
                if pad_heads and spec.config.n_heads:
                    import dataclasses as _dc
                    tp = 16
                    hq = -(-spec.config.n_heads // tp) * tp
                    hkv = spec.config.n_kv_heads
                    if spec.config.n_kv_heads == spec.config.n_heads:
                        hkv = hq                      # MHA: pad both
                    if hq != spec.config.n_heads or hkv != spec.config.n_kv_heads:
                        g = hq // hkv
                        if (spec.config.n_heads - 1) // g < spec.config.n_kv_heads:
                            run_spec = _dc.replace(
                                spec, config=_dc.replace(
                                    spec.config, pad_q_heads_to=hq,
                                    pad_kv_heads_to=hkv))
                label = f"{aid} x {sname} @ {mesh_name}"
                fname = mdir / f"{aid}__{sname}{tag}.json"
                try:
                    rec = lower_cell(run_spec, shape, mesh, scfg)
                    rec["status"] = "ok"
                    fname.write_text(json.dumps(rec, indent=1))
                    print(f"OK    {label}: compile={rec['seconds_compile']:.1f}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"coll={sum(c['bytes'] for c in rec['collectives'].values())/2**30:.2f} GiB")
                except Exception as e:
                    rec = {"arch": aid, "shape": sname, "mesh": mesh_name,
                           "status": "fail", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    fname.write_text(json.dumps(rec, indent=1))
                    print(f"FAIL  {label}: {type(e).__name__}: {str(e)[:200]}")
                results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    ap.add_argument("--n-micro", type=int, default=0, help="override microbatches")
    ap.add_argument("--remat", default="", choices=["", "none", "dots", "full"])
    ap.add_argument("--sequence-shard", action="store_true")
    ap.add_argument("--moe-strategy", default="", choices=["", "gather", "a2a"])
    ap.add_argument("--pad-heads", action="store_true",
                    help="pad q/kv heads to the model-axis multiple (TP)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()

    arch_ids = [args.arch] if args.arch else list(ARCH_SPECS)
    shape_names = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    step_cfg = None
    if args.n_micro or args.remat or args.sequence_shard or args.moe_strategy:
        step_cfg = StepConfig(n_micro=args.n_micro or 1,
                              remat=args.remat or "full",
                              sequence_shard=args.sequence_shard,
                              moe_strategy=args.moe_strategy or "gather")

    results = run_cells(arch_ids, shape_names, meshes,
                        pathlib.Path(args.out), step_cfg, tag=args.tag,
                        pad_heads=args.pad_heads)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n{n_ok}/{len(results)} cells lowered+compiled OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
