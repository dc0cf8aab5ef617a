"""The LUT kernels' share of their roofline: the least time the LUT
sites' work could take on this chip, over the device time of the LUT
kernels in the trace.

The work is counted by the configuration's architecture module from
the sites' logical shapes (``run.arch.generate_lut``): every activation
element read and written in bf16, plus each hosting layer's served
table bytes (``run.site_bytes``) per layer call.  A lookup does no MXU
work, so the bound is HBM bandwidth.

The kernels are the ops whose name starts with ``lut_``: each Pallas
LUT kernel passes that ``name``, which is its HLO instruction's name in
the ``XLA Ops`` events (``lut_act_stacked.3:tpu_custom_call``), the
match ``lut_ms`` reads (``harness.trace.is_lut``).
"""
from harness.trace import is_lut

BOUND = "hbm"

def read(run):
    if run.kind != "offline" or run.trace is None or not run.calls:
        return None
    secs = run.trace.op_time(is_lut)
    if secs <= 0:
        return None
    mix = run.mix
    nbytes = len(run.calls) * run.arch.generate_lut(
        run.m, mix["batch"], mix["prompt_len"], mix["new_tokens"],
        run.site_bytes)
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / secs
