"""Median over the traced window's ``repro:generate`` spans of the summed
duration of the ``repro:lower.*`` spans inside each: tracing and
lowering the call's programs (program spans on the profiler's clock)."""
import statistics

from harness import spans


def read(run):
    if run.kind != "offline" or run.trace is None:
        return None
    per_call = spans.per_call_s(run.trace, spans.LOWER)
    return 1e3 * statistics.median(per_call) if per_call else None
