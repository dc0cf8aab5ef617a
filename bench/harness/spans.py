"""The program's own spans in a reduced trace.

``repro.obs.span`` writes each span as a host annotation named
``repro:<span>`` into the profiler trace, on the device trace's clock
(``harness.trace.Reduced.host``).  Readers match on the name alone.  A
program without such spans yields no calls, and its readers return
``None``.
"""
from __future__ import annotations

from . import trace

CALL = "repro:generate"
LOWER = "repro:lower."
COMPILE = "repro:compile."


def calls(red) -> list:
    """``(start, end)`` of each ``repro:generate`` span, in order."""
    return sorted((s, e) for n, s, e in red.host if n == CALL)


def intervals(red, prefix: str) -> list:
    return [(s, e) for n, s, e in red.host if n.startswith(prefix)]


def per_call_s(red, prefix: str) -> list:
    """For each ``repro:generate`` span, the summed seconds of the spans
    named ``<prefix>...`` that lie inside it (0 for a call with none)."""
    inner = intervals(red, prefix)
    return [sum(e - s for s, e in inner if lo <= s and e <= hi)
            for lo, hi in calls(red)]


def _merged(iv) -> list:
    return trace._merged([trace.Op("", s, e, "") for s, e in iv])


def overlap_s(a, b) -> float:
    """Seconds that the union of intervals ``a`` shares with the union of
    intervals ``b``."""
    a, b = _merged(a), _merged(b)
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot
