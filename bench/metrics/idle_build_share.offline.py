"""The share of the traced window in which chip 0 is idle while the host
lowers or compiles the call's programs: chip 0's idle gaps intersected
with the union of the ``repro:lower.*`` and ``repro:compile.*`` spans,
over the window (device trace and program spans, one clock).  It never
exceeds ``idle_share.offline`` on one chip."""
from harness import spans


def read(run):
    if run.kind != "offline" or run.trace is None:
        return None
    red = run.trace
    if not spans.calls(red):
        return None
    lo, hi = red.window
    build = [(max(s, lo), min(e, hi))
             for s, e in spans.intervals(red, spans.LOWER)
             + spans.intervals(red, spans.COMPILE) if e > lo and s < hi]
    return 100.0 * spans.overlap_s(red.gaps(), build) / red.window_s
