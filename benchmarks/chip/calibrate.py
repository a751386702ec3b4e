#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds a,b,... [--control-seeds x,y,z] [--fault-seeds u,v,w]

For each of ``--seeds`` it serves the cell's traffic through the program,
as the timed path does (the same ``search_auto`` call at the same batch,
or the same insert-and-compact step), and prints the compared numbers:
their largest over the seeds is each number's lower reading. For each of
``--control-seeds`` it puts the control (``benchlib/control.py``: the
reference on the device at ``Precision.HIGH``, and at ``DEFAULT``) in the
program's place, and for each of ``--fault-seeds`` it plants each fault
the cell can have; these give the upper readings. One JSON line per
reading on standard output. It is never run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchlib import control, data, harness


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def numbers(answers, pool, ref_db, cell) -> dict:
    tally = harness.check_pool(pool, answers, cell.cfg["search"]["k"],
                               ref_db, cell.cfg["guarantees"]["exact_routes"])
    return dict(tally.numbers(0), recall_at_10=tally.recall_at_k(),
                routes=tally.routes)


def search_readings(cell, db, index, args):
    search = cell.cfg["search"]
    ref_db = (db.xb, db.attr, db.spec)
    answers = {}
    for seed in args.seeds:
        pool = data.search_pool(db, cell.mix, seed)
        t = time.perf_counter()
        answers[seed] = [harness.serve(index, b, search) for b in pool]
        served = time.perf_counter() - t
        emit(kind="program", seed=seed, serve_s=served,
             **numbers(answers[seed], pool, ref_db, cell))
    for seed in args.fault_seeds:
        pool = data.search_pool(db, cell.mix, seed)
        ans = answers.get(seed) or [harness.serve(index, b, search)
                                    for b in pool]
        emit(kind="fault:altered", seed=seed, **numbers(
            [control.altered(a, db.xb.shape[0], seed + j)
             for j, a in enumerate(ans)], pool, ref_db, cell))
        emit(kind="fault:half", seed=seed, **numbers(
            [control.half(a, seed + j) for j, a in enumerate(ans)],
            pool, ref_db, cell))
    return ref_db


def control_readings(cell, ref_db, seeds, pool_of):
    xb, attr, spec = ref_db
    for prec in control.PASSES:
        serve = control.control_server(xb, attr, spec,
                                       cell.cfg["search"]["k"], prec)
        for seed in seeds:
            pool = pool_of(seed)
            emit(kind=f"control:{prec}", seed=seed, **numbers(
                [serve(b) for b in pool], pool, ref_db, cell))


def ingest_readings(cell, db, index, args):
    from repro.stream import StreamingJAGIndex
    search, check = cell.cfg["search"], cell.mix["check"]

    def state(seed, insert=True):
        rows, rattr = data.ingest_rows(db, cell.mix, seed)
        st = StreamingJAGIndex(index)
        if insert:
            st.insert(rows, harness.attr_table(db.spec["kind"], rattr),
                      auto_compact=False)
            st.compact()
        pool = data.search_pool(db, check, seed)
        data.plant_readback(pool, rows, rattr,
                            int(check["readback_per_batch"]), seed)
        ref_db = (np.concatenate([db.xb, rows]),
                  np.concatenate([db.attr, rattr]), db.spec)
        return st, pool, ref_db

    for seed in args.seeds:
        st, pool, ref_db = state(seed)
        emit(kind="program", seed=seed, **numbers(
            [harness.serve(st, b, search) for b in pool], pool, ref_db,
            cell))
    for seed in args.fault_seeds:
        st, pool, ref_db = state(seed, insert=False)
        emit(kind="fault:unchanged", seed=seed, **numbers(
            [harness.serve(st, b, search) for b in pool], pool, ref_db,
            cell))
        st, pool, ref_db = state(seed)
        ans = [harness.serve(st, b, search) for b in pool]
        emit(kind="fault:altered", seed=seed, **numbers(
            [control.altered(a, ref_db[0].shape[0], seed + j)
             for j, a in enumerate(ans)], pool, ref_db, cell))
    for prec in control.PASSES:
        for seed in args.control_seeds:
            _, pool, ref_db = state(seed, insert=False)
            serve = control.control_server(*ref_db, search["k"], prec)
            emit(kind=f"control:{prec}", seed=seed, **numbers(
                [serve(b) for b in pool], pool, ref_db, cell))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.enable_compile_cache()
    db = data.database(cell.cfg)
    index, built = harness.open_index(cell, db, harness.ROOT, devs)
    emit(kind="index", built=built, device=devs[0].device_kind)
    if cell.mix["kind"] == "search":
        ref_db = search_readings(cell, db, index, args)
        control_readings(cell, ref_db, args.control_seeds,
                         lambda s: data.search_pool(db, cell.mix, s))
    else:
        ingest_readings(cell, db, index, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
