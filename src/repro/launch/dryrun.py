import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the first import side effect: the XLA_FLAGS line above runs before
any jax import so make_mesh can build the 512-device production meshes on
this CPU-only host (dry-run only — tests/benches see 1 device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k --mesh multi                             # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --out results.json

Per cell: jit(step).lower(*abstract).compile() on the (16,16) single-pod
mesh AND the (2,16,16) multi-pod mesh; prints memory_analysis() (proves it
fits 16 GiB/chip) and cost_analysis(); records the roofline terms
(launch/roofline.py) into EXPERIMENTS.md's tables via --out JSON.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from ..configs import all_archs, make_cell
from ..distributed.sharding import use_rules
from .mesh import HW, make_production_mesh
from . import roofline as RL


def _compile(cell, mesh):
    with jax.set_mesh(mesh), use_rules(cell["rules"]):
        jitted = jax.jit(cell["fn"],
                         in_shardings=cell["in_shardings"],
                         out_shardings=cell["out_shardings"],
                         donate_argnums=cell["donate_argnums"])
        return jitted.lower(*cell["args"]).compile()


def run_cell(arch: str, shape: str, mesh_name: str, verbose: bool = True):
    from ..configs import get as get_arch
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    t0 = time.time()
    cell = make_cell(arch, shape, mesh, lowering="unroll")
    compiled = _compile(cell, mesh)
    txt = compiled.as_text()
    r = RL.analyze(arch, shape, mesh_name, n_chips, compiled,
                   cell["model_flops"], hlo_text=txt,
                   flops_scale=cell.get("flops_scale", 1.0),
                   analytic_only=cell.get("analytic_only", False))
    # memory proof from the production (scan/remat) lowering for the cells
    # whose activation accounting depends on it (LM train/prefill)
    spec = get_arch(arch)
    mem_compiled = compiled
    if (spec.family == "lm"
            and spec.shapes[shape]["kind"] in ("train", "prefill")):
        mem_compiled = _compile(
            make_cell(arch, shape, mesh, lowering="scan"), mesh)
    dt = time.time() - t0
    ma = None
    try:
        ma = mem_compiled.memory_analysis()
        r.mem_per_device = float(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    except Exception:
        pass
    if verbose:
        print(f"[{arch} x {shape} x {mesh_name}] compiled in {dt:.1f}s  "
              f"params={cell['n_params'] / 1e9:.2f}B")
        if ma is not None:
            print(f"  memory_analysis: args="
                  f"{ma.argument_size_in_bytes / 2**30:.2f}GiB "
                  f"out={ma.output_size_in_bytes / 2**30:.2f}GiB "
                  f"alias={ma.alias_size_in_bytes / 2**30:.2f}GiB "
                  f"temp={ma.temp_size_in_bytes / 2**30:.2f}GiB "
                  f"(HBM/chip = {HW['hbm_bytes'] / 2**30:.0f}GiB)")
        ca = compiled.cost_analysis() or {}
        print(f"  cost_analysis: flops/chip={ca.get('flops', 0):.3e} "
              f"bytes/chip={ca.get('bytes accessed', 0):.3e}")
        print("  " + RL.format_row(r))
        fit = (r.mem_per_device or 0) <= HW["hbm_bytes"]
        print(f"  fits-HBM: {fit}")
    d = r.to_dict()
    d["compile_s"] = dt
    d["n_params"] = cell["n_params"]
    return d


def default_cells():
    cells = []
    for aid, spec in sorted(all_archs().items()):
        for shape in spec.shapes:
            cells.append((aid, shape))
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-jag", action="store_true")
    args = ap.parse_args(argv)

    cells = default_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    if args.skip_jag:
        cells = [c for c in cells if c[0] != "jag"]
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    results, failures = [], []
    for aid, shape in cells:
        for mesh_name in meshes:
            try:
                results.append(run_cell(aid, shape, mesh_name))
            except Exception as e:
                traceback.print_exc()
                failures.append(
                    {"arch": aid, "shape": shape, "mesh": mesh_name,
                     "error": f"{type(e).__name__}: {e}"})
            if args.out:
                with open(args.out, "w") as f:
                    json.dump({"results": results, "failures": failures},
                              f, indent=1)
    print(f"\n=== dry-run complete: {len(results)} ok, "
          f"{len(failures)} failed ===")
    for f_ in failures:
        print("  FAILED:", f_)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
