"""The plain reference against a loop over every row, and the recall
arithmetic against ``repro.core.recall.recall_at_k``'s convention."""
import numpy as np
import pytest

from benchlib import data, reference


def naive(xb, attr, spec, batch, k):
    ids = np.full((len(batch.queries), k), -1)
    for i, q in enumerate(batch.queries.astype(np.float64)):
        ok = np.flatnonzero(reference.passes(spec, attr, batch.filt, i))
        d2 = ((xb[ok].astype(np.float64) - q) ** 2).sum(1)
        top = ok[np.lexsort((ok, d2))[:k]]
        ids[i, :top.size] = top
    return ids


@pytest.mark.parametrize("spec,fspec", [
    ({"kind": "range"},
     {"kind": "range", "levels": [1.0, 0.3, 0.01, 0.001], "stratified": True}),
    ({"kind": "label", "labels": 12}, {"kind": "label", "labels": 12}),
])
def test_reference_matches_a_loop(spec, fspec):
    cfg = {"rows": 3000, "dim": 24,
           "dataset": {"data_seed": 5, "attribute": spec}}
    db = data.database(cfg)
    pool = data.search_pool(db, {"batch": 40, "pool_batches": 2,
                                 "filter": fspec}, 2**31 + 9)
    ref = reference.Reference(db.xb, db.attr, db.spec)
    for b in pool:
        ids, d2 = ref.topk(b, 10)
        assert (ids == naive(db.xb, db.attr, db.spec, b, 10)).all()
        assert np.isinf(d2[ids < 0]).all()


def test_recall_convention():
    ref = np.array([[1, 2, 3], [-1, -1, -1], [4, -1, -1]])
    served = np.array([[3, 9, 1], [5, 6, 7], [-1, -1, -1]])
    assert reference.recall(served, ref).tolist() == [2 / 3, 1.0, 0.0]
