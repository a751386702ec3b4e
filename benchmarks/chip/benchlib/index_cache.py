"""Build a configuration's index once per checkout, then load it.

The index build is the largest part of a cell's set-up (about six minutes
for 2^17 rows on one TPU v5e), and the database is fixed by the
configuration's ``data_seed``, so the built index is written with
``JAGIndex.save`` under ``benchmarks/chip/cache/index/`` and loaded by
later runs. The file name carries a hash of the configuration as run, the
benchmark's data generator and every ``src/repro/**/*.py``: a change to
any of them builds a new index, and a stale one is never loaded.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path


def key(root: Path, cfg: dict) -> str:
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    files = [Path(__file__).with_name("data.py")]
    files += sorted((root / "src" / "repro").rglob("*.py"))
    for f in files:
        h.update(str(f.relative_to(root)).encode())
        h.update(f.read_bytes())
    return h.hexdigest()[:20]


def load_or_build(root: Path, cache_dir: Path, cfg: dict, build, load,
                  part: str = ""):
    """``(index, built)``: the cached index if its file exists, else
    ``build()`` saved there first. ``load(path)`` reads a saved index.
    ``part`` names one shard of a sharded configuration."""
    stem = cfg["name"] + part
    path = cache_dir / "index" / f"{stem}-{key(root, cfg)}.npz"
    if path.exists():
        return load(str(path)), False
    index = build()
    path.parent.mkdir(parents=True, exist_ok=True)
    for old in path.parent.glob(f"{stem}-{'?' * 20}.npz"):
        old.unlink()
    part = path.with_suffix(".part.npz")
    index.save(str(part))
    os.replace(part, path)
    return index, True
