"""Device time per call of the build's insert program
(``core/build.make_insert_step``, module ``jit_insert``) in the traced
compaction."""
from benchlib import xplane


def read(ctx):
    if "trace" not in ctx:
        return None
    execs = xplane.modules_in_window(ctx["trace"], "jit_insert")
    if not execs:
        return None
    return sum(d for _, _, d in execs) / len(execs) / 1e6
