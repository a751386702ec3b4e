"""Host time per traced batch in per-query dispatch: the self time of the
spans ``jag.gather:*``, ``jag.execute:*`` and ``jag.scatter`` (what is
left of each after the spans nested in it, such as a compile)."""
from benchlib import spans


def read(ctx):
    sp = spans.load(ctx)
    stages = spans.named(sp, "gather:", "execute:", "scatter")
    if not stages:
        return None
    return sum(spans.self_ns(sp, e) for e in stages) / ctx["batches"] / 1e6
