import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init).  Each cell:

    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=...).lower(**input_specs)
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

and writes a JSON artifact under var/dryrun/ that EXPERIMENTS.md's
Dry-run and Roofline sections (and benchmarks/roofline_report.py) read.

Usage:
    python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    python -m repro.launch.dryrun --all --mesh pod
Hillclimb knobs: --microbatches N --no-fsdp --no-remat --tag <name>
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_configs, cell_is_runnable, get_config
from repro.configs import base
from repro.launch import roofline as rl
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf
from repro.optim.adamw import AdamWConfig
from repro.sharding import rules
from repro.train.state import abstract_train_state, train_state_shardings
from repro.train.step import make_decode_step, make_prefill_step, make_train_step

ART_DIR = os.environ.get(
    "REPRO_DRYRUN_DIR",
    os.path.join(os.path.dirname(__file__), "..", "..", "..", "var",
                 "dryrun"))


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(mesh, policy, batch_specs: Dict[str, Any]):
    specs = rules.batch_sharding_specs(policy, batch_specs)
    return {k: NamedSharding(mesh, s) for k, s in specs.items()}


def _mem_dict(mem) -> Dict[str, float]:
    if mem is None:
        return {}
    fields = ["generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"]
    out = {}
    for f in fields:
        try:
            v = getattr(mem, f, None)
            if v is not None:
                out[f] = float(v)
        except Exception:
            pass
    return out


def _cost_dict(cost) -> Dict[str, float]:
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        return {str(k): float(v) for k, v in dict(cost).items()
                if isinstance(v, (int, float))}
    except Exception:
        return {}


def _lower_cell(cfg, shape, mesh, policy, microbatches, remat, unroll):
    """Build + lower one cell's step function.  Returns the lowered
    computation."""
    if shape.step == "train":
        state_specs = abstract_train_state(cfg)
        state_sh = train_state_shardings(state_specs, mesh, policy)
        batch_specs = sp.train_input_specs(cfg, shape)
        batch_sh = _batch_shardings(mesh, policy, batch_specs)
        step = make_train_step(cfg, AdamWConfig(),
                               microbatches=microbatches, remat=remat,
                               unroll=unroll)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,))
        return jitted.lower(state_specs, batch_specs)
    if shape.step == "prefill":
        param_specs_ = tf.param_specs(cfg)
        param_sh = rules.param_sharding_tree(param_specs_, mesh, policy)
        batch_specs = sp.prefill_input_specs(cfg, shape)
        batch_sh = _batch_shardings(mesh, policy, batch_specs)
        step = make_prefill_step(cfg, unroll=unroll)
        jitted = jax.jit(step, in_shardings=(param_sh, batch_sh))
        return jitted.lower(param_specs_, batch_specs)
    # decode
    param_specs_ = tf.param_specs(cfg)
    param_sh = rules.param_sharding_tree(param_specs_, mesh, policy)
    cache_specs_ = sp.decode_cache_specs(cfg, shape)
    cache_sh = _named(mesh, rules.cache_specs(cfg, policy, cache_specs_))
    batch_specs = sp.decode_input_specs(cfg, shape)
    batch_sh = _batch_shardings(mesh, policy, batch_specs)
    step = make_decode_step(cfg, unroll=unroll)
    jitted = jax.jit(step, in_shardings=(param_sh, cache_sh, batch_sh),
                     donate_argnums=(1,))
    return jitted.lower(param_specs_, cache_specs_, batch_specs)


def _measure(cfg, shape, mesh, policy, microbatches, remat) -> Dict[str, float]:
    """Compile a reduced-depth UNROLLED variant and harvest per-device
    FLOPs / bytes / collective bytes (exact per-op accounting).  Inner
    scans (attention KV chunks, SSM chunks) are unrolled too, so loop
    carries -- which TPU aliases in place -- don't get charged as copy
    traffic by the CPU-backend cost analysis."""
    from repro.models import layers as model_layers
    model_layers.set_inner_unroll(True)
    try:
        lowered = _lower_cell(cfg, shape, mesh, policy, microbatches,
                              remat, unroll=True)
        compiled = lowered.compile()
    finally:
        model_layers.set_inner_unroll(False)
    cost = _cost_dict(compiled.cost_analysis())
    coll = rl.parse_collective_bytes(compiled.as_text())
    return {
        "flops": cost.get("flops", 0.0),
        "bytes": cost.get("bytes accessed", 0.0),
        "collective_bytes": rl.collective_total(coll),
        "collectives": coll,
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str = "pod",
             microbatches: int = 1, fsdp: bool = True, remat: bool = True,
             unroll: bool = True, tag: str = "", save: bool = True,
             extra_notes: str = "", levers: Dict[str, Any] | None = None
             ) -> Dict[str, Any]:
    """One dry-run cell.

    Structure: (1) the FULL config is lowered + compiled with scan over
    layers -- this is the multi-pod dry-run proof and provides the memory
    analysis; (2) because HloCostAnalysis counts loop bodies once, exact
    FLOPs/bytes/collectives are measured on 1- and 2-layer-unit UNROLLED
    variants and extrapolated linearly (layers are homogeneous, so the
    per-unit slope is exact)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if levers:
        cfg = _dc.replace(cfg, **levers)
    shape = SHAPES[shape_name]
    runnable, why = cell_is_runnable(cfg, shape)
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "microbatches": microbatches, "fsdp": fsdp, "remat": remat,
        "tag": tag, "notes": extra_notes,
    }
    if not runnable:
        record.update({"status": "skipped", "reason": why})
        return _finish(record, save)

    multi_pod = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    record["n_devices"] = int(mesh.devices.size)
    policy = rules.for_mesh(mesh, fsdp=fsdp)

    with jax.set_mesh(mesh):
        # ---- (1) full-config compile (scan): the dry-run proof ----
        t0 = time.time()
        lowered = _lower_cell(cfg, shape, mesh, policy, microbatches,
                              remat, unroll=False)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)
        try:
            record["memory"] = _mem_dict(compiled.memory_analysis())
        except Exception as e:  # pragma: no cover
            record["memory_analysis_error"] = str(e)
        record["cost_scan_counted_once"] = _cost_dict(
            compiled.cost_analysis())
        record["hlo_bytes"] = len(compiled.as_text())

        # ---- (2) exact cost accounting via 1/2-unit unrolled builds ----
        units = base.layer_units(cfg)
        u1, u2 = (1, 2) if units >= 2 else (units, units)
        t2 = time.time()
        m1 = _measure(base.with_layer_units(cfg, u1), shape, mesh, policy,
                      microbatches, remat)
        m2 = (m1 if u2 == u1 else
              _measure(base.with_layer_units(cfg, u2), shape, mesh,
                       policy, microbatches, remat))
        record["measure_s"] = round(time.time() - t2, 2)

        def extrap(key):
            if u2 == u1:
                return m2[key]
            slope = (m2[key] - m1[key]) / (u2 - u1)
            return m2[key] + (units - u2) * slope

        flops_dev = extrap("flops")
        bytes_dev = extrap("bytes")
        coll_dev = extrap("collective_bytes")
        record["measure_points"] = {
            "units": [u1, u2], "full_units": units,
            "flops": [m1["flops"], m2["flops"]],
            "bytes": [m1["bytes"], m2["bytes"]],
            "collective_bytes": [m1["collective_bytes"],
                                 m2["collective_bytes"]],
        }
        record["collectives_u2"] = m2["collectives"]

    record["cost"] = {"flops": flops_dev, "bytes accessed": bytes_dev}
    record["collective_bytes_per_device"] = coll_dev
    mf = rl.model_flops(cfg, shape)
    terms = rl.roofline_terms(flops_dev, bytes_dev, coll_dev,
                              record["n_devices"], mf)
    record["roofline"] = terms.as_dict()
    record["status"] = "ok"
    return _finish(record, save)


def _finish(record: Dict[str, Any], save: bool) -> Dict[str, Any]:
    if save:
        os.makedirs(ART_DIR, exist_ok=True)
        tag = f"__{record['tag']}" if record.get("tag") else ""
        path = os.path.join(
            ART_DIR,
            f"{record['arch']}__{record['shape']}__{record['mesh']}{tag}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        record["artifact"] = path
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--scan", action="store_true",
                    help="scan over layers instead of unrolling (faster "
                         "compile, but HloCostAnalysis counts loop bodies "
                         "once)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--moe-ep", action="store_true",
                    help="shard_map expert-parallel MoE dispatch")
    ap.add_argument("--attn-bf16", action="store_true",
                    help="bf16 attention probabilities")
    ap.add_argument("--logits-bf16", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list(all_configs()) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    for arch, shape in cells:
        tag = f"__{args.tag}" if args.tag else ""
        path = os.path.join(ART_DIR, f"{arch}__{shape}__{args.mesh}{tag}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {arch} x {shape} ({args.mesh}) exists")
            continue
        print(f"[cell] {arch} x {shape} ({args.mesh}) ...", flush=True)
        try:
            levers = {}
            if args.moe_ep:
                levers["moe_shardmap_ep"] = True
            if args.attn_bf16:
                levers["attn_probs_bf16"] = True
            if args.logits_bf16:
                levers["logits_bf16"] = True
            rec = run_cell(arch, shape, args.mesh,
                           microbatches=args.microbatches,
                           fsdp=not args.no_fsdp, remat=not args.no_remat,
                           tag=args.tag, levers=levers)
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"compute={r['compute_s']:.4f}s "
                      f"memory={r['memory_s']:.4f}s "
                      f"collective={r['collective_s']:.4f}s "
                      f"dominant={r['dominant']} "
                      f"frac={r['roofline_fraction']:.3f}", flush=True)
            else:
                print(f"  {rec['status']}: {rec.get('reason','')}", flush=True)
        except Exception:
            print(f"  FAILED:\n{traceback.format_exc()}", flush=True)
            rec = {"arch": arch, "shape": shape, "mesh": args.mesh,
                   "tag": args.tag, "status": "failed",
                   "error": traceback.format_exc()}
            _finish(rec, True)


if __name__ == "__main__":
    main()
