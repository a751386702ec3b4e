"""Each cell's code path end to end on the CPU at a tiny size: set-up,
window, check, result line. A rehearsal for the chip, never a measurement:
the numbers it prints are the CPU's."""
import json

import pytest

from conftest import CELLS, tiny_run
from benchlib import harness


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    res = tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in harness.load_cell(cell).end_to_end}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_no_chip_means_no_result(capsys):
    with pytest.raises(harness.NoChip):
        harness.run("range-mixed", 1, 1.0, False)
    assert capsys.readouterr().out == ""


def test_same_seed_same_traffic():
    from benchlib import data
    cell = harness.load_cell("range-mixed")
    db = data.Database(None, __import__("numpy").zeros((32, 4)), None,
                       cell.cfg["dataset"]["attribute"])
    a = data.search_pool(db, cell.mix, 2**31 + 5)
    b = data.search_pool(db, cell.mix, 2**31 + 5)
    assert all((x.queries == y.queries).all() and
               (x.filt["lo"] == y.filt["lo"]).all() for x, y in zip(a, b))
    counts = sorted(__import__("numpy").unique(a[0].level,
                                               return_counts=True)[1])
    assert counts == [42, 42, 43, 43, 43, 43]
