"""Host time per compaction spent in first calls that trace and compile a
program (spans ``jag.jit:*`` inside ``jag.compact``: the insert step and
the overflow re-prune, which ``compact()`` jits anew on every call)."""
from benchlib import spans


def read(ctx):
    sp = spans.load(ctx)
    compacts = spans.named(sp, "compact")
    if not compacts:
        return None
    total = sum(e[2] - e[1] for c in compacts
                for e in spans.named(spans.inside(sp, c), "jit:"))
    return total / len(compacts) / 1e6
