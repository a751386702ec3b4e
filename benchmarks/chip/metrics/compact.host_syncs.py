"""Device-to-host reads per compaction: spans ``jag.sync:*`` inside
``jag.compact`` (``finalize_graph`` reads the degrees once per re-prune
pass, and once more to see that none is left)."""
from benchlib import spans


def read(ctx):
    sp = spans.load(ctx)
    compacts = spans.named(sp, "compact")
    if not compacts:
        return None
    n = sum(len(spans.named(spans.inside(sp, c), "sync:")) for c in compacts)
    return float(n) / len(compacts)
