"""LUT kernel device time over device busy time (device trace); the
kernels are the ones ``lut_roofline`` names."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_lut_roofline_names",
    Path(__file__).with_name("lut_roofline.py"))
_names = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_names)


def read(run):
    if run.kind != "offline" or run.trace is None:
        return None
    busy = run.trace.busy_s()
    secs = run.trace.op_time(_names.is_lut)
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy
