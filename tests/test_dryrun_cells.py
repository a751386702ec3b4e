"""Dry-run cell construction + lowering smoke (subprocess: 512 fake
devices). Full compiles live in launch/dryrun.py; here we verify the
registry produces lowerable cells for one representative of each family
quickly (trace-only)."""
import os
import subprocess
import sys


def test_trace_representative_cells():
    code = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys; sys.path.insert(0, "src")
import jax
from repro.configs import make_cell
from repro.distributed.sharding import use_rules
from repro.launch.mesh import make_production_mesh
cells = [("fm", "retrieval_cand"), ("gcn-cora", "molecule"),
         ("qwen3-1.7b", "decode_32k"), ("jag", "serve_1b")]
for mp in (False, True):
    mesh = make_production_mesh(multi_pod=mp)
    for arch, shape in cells:
        cell = make_cell(arch, shape, mesh)
        with jax.set_mesh(mesh), use_rules(cell["rules"]):
            jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                    out_shardings=cell["out_shardings"],
                    donate_argnums=cell["donate_argnums"]).lower(
                        *cell["args"])
print("TRACE_OK")
'''
    r = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert "TRACE_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


def test_registry_counts():
    from repro.configs import all_archs, get
    archs = all_archs()
    cells = sum(len(get(a).shapes) for a in archs if a != "jag")
    assert cells == 40, cells  # the assigned 40 (arch x shape) cells
    assert len(get("jag").shapes) == 2
