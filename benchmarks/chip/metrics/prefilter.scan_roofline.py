"""Share of its roofline that the prefilter route reaches: the least time
the chip needs for the exact scan's work (``work.prefilter_scan``, at the
published peaks) over the device time of the route's programs. It reads
the whole route program, not only the Pallas kernel, so a change in how
the scan is done is read against the same work."""
from benchlib import work, xplane


def read(ctx):
    execs = [e for e in xplane.route_execs(ctx) if e["route"] == "prefilter"]
    if not execs or ctx["peaks"] is None:
        return None
    attr_bytes = ctx["cfg"]["dataset"]["attribute"]["bytes_per_row"]
    least = 0.0
    for e in execs:
        (rows, dim), (queries, _) = e["shapes"][0], e["shapes"][2]
        least += work.least_seconds(
            work.prefilter_scan(queries, rows, dim, attr_bytes), ctx["peaks"])
    return 100.0 * least / (sum(e["dur_ns"] for e in execs) / 1e9)
