"""The program's own spans (``jag.*``, ``repro.obs.spans``) in a traced
window, and the arithmetic the per-layer readers share.

The program emits each span as a ``jax.profiler.TraceAnnotation``, so it
lies on the profiler's host plane on the same clock as the device ops.
:func:`load` reads them, with their arguments, from the newest
``.xplane.pb`` under ``cache/trace/``, and only when that file is the one
``ctx["trace"]`` was reduced from: its ``bench.window`` must start where
``xplane.window(ctx["trace"])`` does. A program without these spans (one
that predates them) yields an empty list, and every reader then returns
None.

A span is ``(name, start_ns, end_ns, args, line)``, the name without the
``jag.`` prefix; ``line`` is the host thread, since spans nest by time
within one thread.

The profiler puts the chip's events on the host's clock with an offset of
its own: on one TPU v5e, programs appeared 1.1 to 1.25 ms before the host
span that launched them. :func:`idle` therefore moves the chip's idle gaps
by :func:`skew_ns`, the least shift that starts every program after the
span that launched it, before they are laid over the host's spans.
"""
from __future__ import annotations

import glob
import os

from . import harness, xplane

PREFIX = "jag."
# the span that launches each program other than an executor route, whose
# program ``jit_<route>`` is launched inside ``execute:<route>``
LAUNCHED_IN = {"jit_estimate_selectivity": "plan.probe",
               "jit_insert": "compact.insert", "jit_reprune": "compact.reprune",
               "jit_delta": "delta", "jit_merge": "merge"}


def from_file(path: str) -> tuple:
    """(start_ns of ``bench.window`` or None, the ``jag.*`` spans) of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    start, out = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == xplane.WINDOW and start is None:
                    start = int(e.start_ns)
                elif e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append((e.name[len(PREFIX):], s,
                                s + int(e.duration_ns),
                                {key: v for key, v in e.stats},
                                f"{plane.name}/{k}"))
    return start, sorted(out, key=lambda e: (e[1], -e[2]))


def load(ctx: dict) -> list:
    """The ``jag.*`` spans of the run's traced window (cached in ``ctx``);
    empty where the trace holds none or the newest trace file is not the
    one ``ctx["trace"]`` came from."""
    if "spans" not in ctx:
        ctx["spans"] = []
        files = sorted(glob.glob(os.path.join(str(harness.CACHE / "trace"),
                                              "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if files and "trace" in ctx:
            start, found = from_file(files[-1])
            if start == xplane.window(ctx["trace"])[0]:
                ctx["spans"] = found
    return ctx["spans"]


def named(spans: list, *prefixes: str) -> list:
    """The spans whose name is one of ``prefixes`` or starts with one that
    ends in ``:``."""
    return [e for e in spans
            if e[0] in prefixes or any(p.endswith(":") and
                                       e[0].startswith(p) for p in prefixes)]


def inside(spans: list, outer: tuple) -> list:
    """The spans nested in ``outer``: same thread, within its time."""
    return [e for e in spans if e is not outer and e[4] == outer[4]
            and outer[1] <= e[1] and e[2] <= outer[2]]


def _intervals(spans):
    return [(None, s, e - s) for _, s, e, _, _ in spans]


def self_ns(spans: list, outer: tuple) -> int:
    """Time in ``outer`` outside every span nested in it."""
    lo, hi = outer[1], outer[2]
    return (hi - lo) - xplane.union_ns(_intervals(inside(spans, outer)),
                                       lo, hi)


def skew_ns(ctx: dict) -> int:
    """How far the first chip's events in the trace run ahead of the
    host's clock: the largest lead of a program's start over the start of
    the span that launched it (the k-th program of a name pairs with the
    k-th such span, where their counts agree), and 0 where none leads."""
    sp = load(ctx)
    execs = {}
    for m in xplane.modules_in_window(ctx["trace"]):
        execs.setdefault(m[0], []).append(m)
    lead = 0
    for name, ms in execs.items():
        launcher = LAUNCHED_IN.get(name, "execute:" + name[len("jit_"):])
        hosts = named(sp, launcher)
        if len(hosts) == len(ms):
            lead = max([lead] + [h[1] - m[1] for h, m in zip(hosts, ms)])
    return lead


def idle(ctx: dict) -> list:
    """The first chip's idle gaps in the window on the host's clock,
    [[start_ns, end_ns]] (moved by :func:`skew_ns`)."""
    tr = ctx["trace"]
    lead = skew_ns(ctx)
    return [[a + lead, b + lead]
            for a, b in xplane.gaps(tr, xplane.device_planes(tr)[0])]


def overlap_ns(gaps: list, spans: list) -> int:
    """Length of the gaps' time covered by at least one of ``spans``."""
    cover = _intervals(spans)
    return sum(xplane.union_ns(cover, a, b) for a, b in gaps)


def has_chip(ctx: dict) -> bool:
    return "trace" in ctx and bool(xplane.device_planes(ctx["trace"]))
