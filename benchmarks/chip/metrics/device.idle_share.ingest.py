"""Share of the traced ingest window in which the device runs no
operation: 1 - union of device-op intervals / window."""
from benchlib import xplane


def read(ctx):
    if "trace" not in ctx:
        return None
    lo, hi = xplane.window(ctx["trace"])
    return 1.0 - xplane.busy_ns(ctx["trace"]) / (hi - lo)
