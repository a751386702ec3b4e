"""Device-idle time per traced batch while the host is inside the planner
(span ``jag.plan``: the selectivity probe's launch, its read back to the
host, ``jag.sync:planner``, and the host banding)."""
from benchlib import spans


def read(ctx):
    plan = spans.named(spans.load(ctx), "plan")
    if not plan or not spans.has_chip(ctx):
        return None
    return spans.overlap_ns(spans.idle(ctx), plan) / ctx["batches"] / 1e6
