"""Device time per batch of the graph route's programs (the JAG
traversal), from the profiler trace."""
from benchlib import xplane


def read(ctx):
    execs = [e for e in xplane.route_execs(ctx) if e["route"] == "graph"]
    if not execs:
        return None
    return sum(e["dur_ns"] for e in execs) / ctx["batches"] / 1e6
