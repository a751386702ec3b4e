"""The benchmark's own data and traffic generator, NumPy only.

The database a configuration holds is drawn from the configuration's fixed
``data_seed``: it is the dataset a deployment keeps, the same in every run,
so its index can be built once per checkout and cached. ``--seed`` draws
only what a client sends: query vectors, their filters, and the rows an
ingest cell inserts.

The distributions are those of ``repro.data.synthetic`` (the JAG paper's
App. D.2 attribute and selectivity structure over clustered Gaussian
vectors), copied here so that no later change to the program can change
the yardstick:

- vectors: 32 Gaussian cluster centres (scale 4), rows and queries are a
  centre plus unit Gaussian noise;
- ``range``: an integer attribute uniform in [0, 1e6); a query asks for
  a closed range of width 1e6 * level;
- ``label``: one label per row, uniform over ``labels`` values; a query
  names one label.

One generator reads every traffic file: the keys it understands are
documented in :func:`search_pool`, :func:`ingest_rows` and
:func:`plant_readback`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

N_CLUSTERS = 32
CENTRE_SCALE = 4.0
RANGE_MAX = 1_000_000

# independent random streams drawn from one seed
_DB, _QUERIES, _FILTERS, _INSERT, _READBACK = range(5)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Database:
    xb: np.ndarray          # float32 [N, d]
    centres: np.ndarray     # float64 [32, d]
    attr: np.ndarray        # range: float32 values; label: int32 labels
    spec: dict              # the configuration's "attribute" entry


@dataclasses.dataclass
class Batch:
    queries: np.ndarray     # float32 [B, d]
    filt: dict              # range: lo, hi float32 [B]; label: label int32 [B]
    level: np.ndarray       # float64 [B]: intended selectivity of each query


def _rows(rng, centres, n):
    asg = rng.integers(0, centres.shape[0], n)
    noise = rng.standard_normal((n, centres.shape[1]), dtype=np.float32)
    return (centres[asg].astype(np.float32) + noise)


def _attr(rng, kind, n, spec):
    if kind == "range":
        return rng.integers(0, RANGE_MAX, n).astype(np.float32)
    return rng.integers(0, int(spec["labels"]), n).astype(np.int32)


def database(cfg: dict) -> Database:
    """The configuration's fixed database, from its ``data_seed``."""
    ds = cfg["dataset"]
    rng = _rng(ds["data_seed"], _DB)
    centres = rng.standard_normal((N_CLUSTERS, cfg["dim"])) * CENTRE_SCALE
    xb = _rows(rng, centres, cfg["rows"])
    attr = _attr(rng, ds["attribute"]["kind"], cfg["rows"], ds["attribute"])
    return Database(xb, centres, attr, ds["attribute"])


def _range_filters(rng, levels, stratified, B):
    levels = np.asarray(levels, np.float64)
    if stratified:
        # equal counts per level (the first B % len levels get one more),
        # in a random order within the batch
        counts = np.full(levels.size, B // levels.size)
        counts[:B % levels.size] += 1
        lv = rng.permutation(np.repeat(levels, counts))
    else:
        lv = rng.choice(levels, B)
    width = RANGE_MAX * lv
    lo = rng.uniform(0.0, np.maximum(RANGE_MAX - width, 1.0))
    lo = lo.astype(np.float32)
    hi = (lo.astype(np.float64) + width).astype(np.float32)
    return {"lo": lo, "hi": hi}, lv


def search_pool(db: Database, mix: dict, seed: int) -> list:
    """``mix["pool_batches"]`` batches of ``mix["batch"]`` queries.

    ``mix["filter"]`` is ``{"kind": "range", "levels": [...],
    "stratified": bool}`` or ``{"kind": "label", "labels": n}``.
    """
    B, P = int(mix["batch"]), int(mix["pool_batches"])
    fspec = mix["filter"]
    rq, rf = _rng(seed, _QUERIES), _rng(seed, _FILTERS)
    pool = []
    for _ in range(P):
        q = _rows(rq, db.centres, B)
        if fspec["kind"] == "range":
            filt, lv = _range_filters(rf, fspec["levels"],
                                      fspec.get("stratified", False), B)
        elif fspec["kind"] == "label":
            lab = rf.integers(0, int(fspec["labels"]), B).astype(np.int32)
            filt, lv = {"label": lab}, np.full(B, 1.0 / fspec["labels"])
        else:
            raise ValueError(f"unknown filter kind {fspec['kind']!r}")
        pool.append(Batch(q, filt, lv))
    return pool


def ingest_rows(db: Database, mix: dict, seed: int):
    """``mix["insert_rows"]`` new rows (vectors, attribute) drawn from
    ``seed``, distributed as the database's rows."""
    rng = _rng(seed, _INSERT)
    n = int(mix["insert_rows"])
    return _rows(rng, db.centres, n), _attr(rng, db.spec["kind"], n, db.spec)


def plant_readback(pool: list, rows: np.ndarray, attr: np.ndarray,
                   per_batch: int, seed: int) -> None:
    """Replace the first ``per_batch`` queries of each batch with inserted
    rows' own vectors, filtered by a range that holds their attribute."""
    rng = _rng(seed, _READBACK)
    pick = rng.choice(rows.shape[0], per_batch * len(pool), replace=False)
    for j, b in enumerate(pool):
        ids = pick[j * per_batch:(j + 1) * per_batch]
        b.queries[:per_batch] = rows[ids]
        if "label" in b.filt:
            b.filt["label"][:per_batch] = attr[ids]
        else:
            width = b.filt["hi"][:per_batch].astype(np.float64) - \
                b.filt["lo"][:per_batch]
            lo = np.maximum(attr[ids] - width / 2, 0.0).astype(np.float32)
            b.filt["lo"][:per_batch] = lo
            b.filt["hi"][:per_batch] = (lo + width).astype(np.float32)
