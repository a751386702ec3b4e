"""A configuration with ``"shards": 4`` on four virtual CPU devices: one
cached JAG shard per device behind ``ShardedJAGIndex``, answers checked
against the reference over the union of the shards' rows. The process
needs its own device count, so the check runs in a child process."""
import json
import os
import subprocess
import sys
from pathlib import Path

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from conftest import TINY
from benchlib import data, harness
sys.path.insert(0, str(harness.ROOT / "src"))
harness.CACHE = __import__("pathlib").Path(sys.argv[3])
over = {"config": dict(TINY["config"], rows=4 * 2048, shards=4),
        "traffic": TINY["traffic"]}
cell = harness.load_cell("range-mixed", overrides=over)
db = data.database(cell.cfg)
index, built = harness.open_index(cell, db, harness.ROOT, jax.devices())
again, built_again = harness.open_index(cell, db, harness.ROOT, jax.devices())
pool = data.search_pool(db, cell.mix, 2**31 + 21)
answers = [harness.serve(index, b, cell.cfg["search"]) for b in pool]
tally = harness.check_pool(pool, answers, 10, (db.xb, db.attr, db.spec),
                           ["prefilter"])
print(json.dumps(dict(tally.numbers(0), shards=index.n_shards,
                      built=built, built_again=built_again)))
"""


def test_four_shards_on_four_devices(tmp_path):
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(here), str(here.parent),
         str(tmp_path)], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["shards"] == 4 and r["built"] and not r["built_again"]
    assert r["short"] == 0 and r["violations"] == 0
    assert r["rank_gap"] < 1e-4 and r["approx_recall"] >= 0.9
