"""Median over the traced window's ``repro:generate`` spans of the summed
duration of the ``repro:compile.*`` spans inside each:
``Lowered.compile()`` of the call's programs, a persistent-cache load
when the cache is warm (program spans on the profiler's clock)."""
import statistics

from harness import spans


def read(run):
    if run.kind != "offline" or run.trace is None:
        return None
    per_call = spans.per_call_s(run.trace, spans.COMPILE)
    return 1e3 * statistics.median(per_call) if per_call else None
