"""Median over the window's calls of ``Generation.prefill_s``, the
program's own host-clock prefill span, which ends in
``block_until_ready``."""
import statistics


def read(run):
    if run.kind != "offline" or not run.calls:
        return None
    return 1e3 * statistics.median(c.prefill_s for c in run.calls)
