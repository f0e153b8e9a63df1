"""HLO diagnosis tool for the perf hillclimb.

Compiles a reduced-depth unrolled variant of one cell and prints the
top-K collectives and top-K tensors by bytes, each attributed to its
source op (op_name metadata) -- the "profile" of the dry-run world.

Usage:
  PYTHONPATH=src python -m benchmarks.hlo_diag --arch yi-34b \
      --shape train_4k --mesh pod --units 1 [--top 15]
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse
import re
from collections import defaultdict


def diagnose(arch: str, shape_name: str, mesh_kind: str = "pod",
             units: int = 1, top: int = 15, microbatches: int = 1,
             fsdp: bool = True, remat: bool = True):
    from repro.configs import get_config, SHAPES, base
    from repro.launch.dryrun import _lower_cell
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import (_SHAPE_RE, _shape_bytes,
                                       COLLECTIVE_OPS)
    from repro.sharding import rules
    from repro.models import layers as model_layers

    cfg = base.with_layer_units(get_config(arch), units)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    policy = rules.for_mesh(mesh, fsdp=fsdp)
    model_layers.set_inner_unroll(True)
    try:
        with jax.set_mesh(mesh):
            compiled = _lower_cell(cfg, shape, mesh, policy, microbatches,
                                   remat, unroll=True).compile()
    finally:
        model_layers.set_inner_unroll(False)
    text = compiled.as_text()

    meta_re = re.compile(r'op_name="([^"]*)"')

    colls, tensors = [], []
    by_source = defaultdict(float)
    for line in text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        lhs, _, rhs = s.partition("=")
        rhs = rhs.strip()
        meta = meta_re.search(s)
        op_name = meta.group(1) if meta else "?"
        matched = False
        for op in COLLECTIVE_OPS:
            m = re.match(r"^(\(?[\w\[\],{}\s/#*]*?\)?)\s*%?" + op
                         + r"(-start)?\(", rhs)
            if m:
                b = sum(_shape_bytes(d, dims)
                        for d, dims in _SHAPE_RE.findall(m.group(1)))
                colls.append((b, op, op_name, rhs[:90]))
                by_source[_short(op_name)] += b
                matched = True
                break
        if not matched:
            m = re.match(r"^(\w+)\[([\d,]*)\]", rhs)
            if m:
                b = _shape_bytes(m.group(1), m.group(2))
                if b > 1e8:
                    tensors.append((b, op_name, rhs[:90]))

    colls.sort(reverse=True)
    tensors.sort(reverse=True)
    total = sum(b for b, *_ in colls)
    print(f"=== {arch} x {shape_name} ({mesh_kind}, {units} units) ===")
    print(f"collective bytes/device: {total / 1e9:.2f} GB "
          f"({len(colls)} ops)\n")
    print("--- top collectives ---")
    for b, op, name, desc in colls[:top]:
        print(f"{b / 1e9:8.2f} GB {op:18s} {_short(name)}")
    print("\n--- collective bytes by source op ---")
    for name, b in sorted(by_source.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{b / 1e9:8.2f} GB {name}")
    print("\n--- largest tensors (>100MB) ---")
    seen = set()
    for b, name, desc in tensors[: top * 2]:
        key = (round(b / 1e7), _short(name))
        if key in seen:
            continue
        seen.add(key)
        print(f"{b / 1e9:8.2f} GB {_short(name)}  {desc[:60]}")
    return colls, tensors


def _short(op_name: str) -> str:
    # keep the trailing, human-meaningful part of the op_name path
    parts = op_name.split("/")
    return "/".join(parts[-3:]) if len(parts) > 3 else op_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod")
    ap.add_argument("--units", type=int, default=1)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args()
    diagnose(args.arch, args.shape, args.mesh, args.units, args.top,
             fsdp=not args.no_fsdp, remat=not args.no_remat)


if __name__ == "__main__":
    main()
