"""Device time per batch of the prefilter route's programs (the exact
masked scan), from the profiler trace."""
from benchlib import xplane


def read(ctx):
    execs = [e for e in xplane.route_execs(ctx) if e["route"] == "prefilter"]
    if not execs:
        return None
    return sum(e["dur_ns"] for e in execs) / ctx["batches"] / 1e6
