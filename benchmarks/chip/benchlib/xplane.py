"""Reduction from a profiler trace to device metrics.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events; everything after it works on that dict, so the
reduction can be checked on a small recorded trace without a chip
(``tests/test_xplane.py``).

- ``busy_ns``: the union of the intervals in which an operation runs on a
  device, clipped to the traced window (the host span ``bench.window``).
- ``modules``: the program executions on a device, in order.
- ``match_programs``: pairs the k-th execution of each program name on the
  device with the k-th launch of that program that the executor logged, so
  that a route's device time is read by route and not by a guess at names.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"


def load(trace_dir: str) -> dict:
    """``{"device": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...]}`` from the newest xplane file
    under ``trace_dir``. Host events are kept only for the benchmark's own
    spans (names starting ``bench.``)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [[e.name, int(e.start_ns),
                                     int(e.duration_ns)]
                                    for e in line.events]
            out["device"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith("bench."))
    return out


def window(trace: dict) -> tuple:
    """(start_ns, end_ns) of the traced window's host span."""
    spans = [e for e in trace["host"] if e[0] == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    _, s, d = spans[0]
    return s, s + d


def _line(lines: dict, word: str):
    for name, events in lines.items():
        if word in name:
            return events
    return None


def op_events(trace: dict, plane: str) -> list:
    """Operation events of one device plane ("XLA Ops"; the module line
    where a backend writes no op line)."""
    lines = trace["device"][plane]
    ev = _line(lines, "XLA Ops")
    return ev if ev is not None else (_line(lines, "Modules") or [])


def module_events(trace: dict, plane: str) -> list:
    """Program executions [name, start_ns, dur_ns] of one device plane, in
    start order, names without the ``(id)`` suffix some backends append."""
    ev = _line(trace["device"][plane], "Modules") or []
    return sorted(([re.sub(r"\(.*\)$", "", n), s, d] for n, s, d in ev),
                  key=lambda e: e[1])


def device_planes(trace: dict) -> list:
    """Names of the TPU chips' planes (not the host's, nor the
    profiler's own ``/device:CUSTOM:*`` planes)."""
    return sorted(p for p in trace["device"] if p.startswith("/device:TPU:"))


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of [start, start + dur) intervals within
    [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for _, s, d in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(trace: dict) -> float:
    """Busy time in the traced window, averaged over the device planes."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    return sum(union_ns(op_events(trace, p), lo, hi)
               for p in planes) / len(planes)


def gaps(trace: dict, plane: str) -> list:
    """Idle gaps [start_ns, end_ns] of one device within the window,
    longest first."""
    lo, hi = window(trace)
    ev = sorted((s, s + d) for _, s, d in op_events(trace, plane))
    out, cur = [], lo
    for s, e in ev:
        if s > cur:
            out.append([cur, min(s, hi)])
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append([cur, hi])
    return sorted((g for g in out if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])


def host_span_at(trace: dict, t: int) -> str:
    """The innermost benchmark host span that holds time ``t``."""
    best = None
    for name, s, d in trace["host"]:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside"


def match_programs(modules: list, programs: list) -> list:
    """Route executions from module events and the executor's launch log.

    ``programs`` is ``[{"module": "jit_run", "route": ..., ...}, ...]`` in
    launch order. The device runs one stream in launch order, so the k-th
    execution of a module name is the k-th launch of that name. Returns a
    copy of each program entry with ``start_ns`` and ``dur_ns`` added;
    raises when the counts disagree, since the pairing would then be
    wrong."""
    by_name = {}
    for name, s, d in modules:
        by_name.setdefault(name, []).append((s, d))
    seen = {}
    out = []
    for p in programs:
        execs = by_name.get(p["module"], [])
        k = seen.get(p["module"], 0)
        if k >= len(execs):
            raise ValueError(f"{p['module']}: {len(execs)} executions on "
                             f"the device, more launches logged")
        seen[p["module"]] = k + 1
        out.append(dict(p, start_ns=execs[k][0], dur_ns=execs[k][1]))
    for name, k in seen.items():
        if k != len(by_name[name]):
            raise ValueError(f"{name}: {len(by_name[name])} executions on "
                             f"the device, {k} launches logged")
    return out


def top_ops(trace: dict, plane: str, owners=(), n: int = 10) -> list:
    """The ``n`` operations that took most device time in the window,
    [[name, seconds], ...]. An operation is named by its HLO instruction,
    prefixed with the label of the program execution that holds it
    (``owners``: [[start_ns, end_ns, label], ...])."""
    lo, hi = window(trace)
    owners = sorted(owners)
    tot, k = {}, 0
    for name, s, d in sorted(op_events(trace, plane), key=lambda e: e[1]):
        t = min(s + d, hi) - max(s, lo)
        if t <= 0:
            continue
        while k < len(owners) and owners[k][1] <= s:
            k += 1
        op = name.split(" = ")[0].lstrip("%")
        if k < len(owners) and owners[k][0] <= s:
            op = f"{owners[k][2]}/{op}"
        tot[op] = tot.get(op, 0) + t
    return [[k_, v / 1e9] for k_, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def owners(ctx: dict) -> list:
    """[[start_ns, end_ns, label], ...] of the program executions in the
    window: the route where the executor logged the launch, else the
    module name."""
    by_start = {e["start_ns"]: e["route"] for e in route_execs(ctx)}
    return [[s, s + d, by_start.get(s, m)]
            for m, s, d in modules_in_window(ctx["trace"])]


def idle_gaps(ctx: dict, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the first chip, [[label, seconds]],
    labelled by the host span they fall in and the programs on either
    side of them on the device."""
    tr = ctx["trace"]
    own = owners(ctx)
    out = []
    for a, b in gaps(tr, device_planes(tr)[0])[:n]:
        before = [o[2] for o in own if o[1] <= a][-1:] or ["start"]
        after = [o[2] for o in own if o[0] >= b][:1] or ["end"]
        out.append([f"{host_span_at(tr, (a + b) // 2)}: {before[0]} -> "
                    f"{after[0]}", (b - a) / 1e9])
    return out


def modules_in_window(trace: dict, name=None) -> list:
    """Program executions that start inside the window on the first
    device plane, optionally of one module name."""
    lo, hi = window(trace)
    plane = device_planes(trace)[0]
    return [m for m in module_events(trace, plane)
            if lo <= m[1] < hi and (name is None or m[0] == name)]


def route_execs(ctx: dict) -> list:
    """The executor's launches in the traced window, each with its device
    start and duration (see :func:`match_programs`); cached in ``ctx``.
    Empty where the run logged no launches."""
    if "route_execs" not in ctx:
        progs = ctx.get("programs") or []
        ctx["route_execs"] = (match_programs(
            modules_in_window(ctx["trace"]), progs) if progs else [])
    return ctx["route_execs"]
