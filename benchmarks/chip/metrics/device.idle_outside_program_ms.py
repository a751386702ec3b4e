"""Device-idle time per traced batch while the host is in no ``jag.*``
span: the client's own work between requests (building the next filter,
reading the last answer), outside the program's reach."""
from benchlib import spans


def read(ctx):
    sp = spans.load(ctx)
    if not sp or not spans.has_chip(ctx):
        return None
    gaps = spans.idle(ctx)
    idle_ns = sum(b - a for a, b in gaps)
    return (idle_ns - spans.overlap_ns(gaps, sp)) / ctx["batches"] / 1e6
