"""LUT kernel device time over device busy time (device trace); the
kernels are the ``lut_`` ones that ``lut_ms`` and ``lut_roofline`` read
(``harness.trace.is_lut``)."""
from harness.trace import is_lut


def read(run):
    if run.kind != "offline" or run.trace is None:
        return None
    busy = run.trace.busy_s()
    secs = run.trace.op_time(is_lut)
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy
