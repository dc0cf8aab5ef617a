"""LUT kernel device time per call: the device time of the ops whose
name starts with ``lut_`` (the Pallas LUT kernels' ``name``, which is
their HLO instruction's name in the ``XLA Ops`` events;
``harness.trace.is_lut``), over the window's calls (device trace)."""
from harness.trace import is_lut


def read(run):
    if run.kind != "offline" or run.trace is None or not run.calls:
        return None
    secs = run.trace.op_time(is_lut)
    return 1e3 * secs / len(run.calls) if secs > 0 else None
