"""Median over the window's calls of ``Generation.decode_s`` over the
decode steps of the call (program span, host clock)."""
import statistics


def read(run):
    if run.kind != "offline" or not run.calls:
        return None
    steps = run.mix["new_tokens"]
    return 1e3 * statistics.median(c.decode_s / steps for c in run.calls)
