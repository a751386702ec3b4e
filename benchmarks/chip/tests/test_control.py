"""``correct`` must come out false for the control and for every fault a
cell can have, at a size a test run holds (the chip readings that set the
limits come from ``calibrate.py``)."""
import pytest

from conftest import CELLS, KIND, TINY, tiny_run
from benchlib import control, data, harness, reference

SEARCH_CELLS = [c for c in CELLS if KIND[c] == "search"]


@pytest.mark.parametrize("cell", SEARCH_CELLS)
def test_control_fails_a_limit(cell):
    """The reference below the configuration's precision, in the
    program's place, fails at least one compared number. The CPU computes
    the chip's control (``Precision.HIGH``) in float32, so this reads the
    one-pass bf16 product; ``calibrate.py`` reads both on the chip."""
    precision = "DEFAULT"
    c = harness.load_cell(cell, overrides=TINY)
    db = data.database(c.cfg)
    pool = data.search_pool(db, c.mix, 2**31 + 3)
    serve = control.control_server(db.xb, db.attr, db.spec,
                                   c.cfg["search"]["k"], precision)
    tally = harness.check_pool(pool, [serve(b) for b in pool],
                               c.cfg["search"]["k"],
                               (db.xb, db.attr, db.spec),
                               c.cfg["guarantees"]["exact_routes"])
    checks = reference.judge(tally.numbers(0),
                             c.cfg["guarantees"]["limits"])
    assert not all(v["ok"] for v in checks.values()), checks


FAULTS = {
    "altered": lambda a: control.altered(a, 2048, 7),
    "half": lambda a: control.half(a, 7),
}


# an ingest step has no batch of answers to halve; its own fault, an
# insert that changes nothing, has a test of its own below
@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in FAULTS
    if not (KIND[c] == "ingest" and f == "half")])
def test_fault_in_timed_path_is_not_correct(cell, fault, monkeypatch):
    """The whole run, with the answers broken where they are produced."""
    serve = harness.serve
    monkeypatch.setattr(harness, "serve",
                        lambda *a: FAULTS[fault](serve(*a)))
    assert tiny_run(cell)["correct"] is False


def test_unchanged_ingest_step_is_not_correct(monkeypatch):
    """An insert that leaves the index unchanged: the inserted rows must
    come back as their own nearest neighbours, and do not."""
    from repro.stream import StreamingJAGIndex
    monkeypatch.setattr(StreamingJAGIndex, "insert",
                        lambda self, *a, **k: {})
    res = tiny_run("range-ingest")
    assert res["correct"] is False
    assert res["checks"]["short"]["value"] > 0 or \
        res["checks"]["rank_gap"]["value"] > res["checks"]["rank_gap"]["limit"]
