"""The LUT kernel's share of its roofline: the least time the MLP LUT
site's work could take on this chip, over the device time of the LUT
kernels in the trace.

The work is counted from the site's logical shapes (``harness.work``):
every activation element read and written in bf16, plus one layer's
served table bytes per layer call.  A lookup does no MXU work, so the
bound is HBM bandwidth.

The kernels are found by the strings below in their ``XLA Ops`` events.
The program's ``pallas_call``s pass no ``name=``, so a Mosaic kernel
shows only as a ``tpu_custom_call`` (with ``kernel_metadata={}``); on the
served path of these cells the Pallas LUT lookups are the only Mosaic
kernels.  Once the kernels carry names, add them here.
"""
from harness import work

KERNELS = ('custom_call_target="tpu_custom_call"',)
BOUND = "hbm"


def is_lut(op) -> bool:
    return any(k in op.meta for k in KERNELS)


def read(run):
    if run.kind != "offline" or run.trace is None or not run.calls:
        return None
    secs = run.trace.op_time(is_lut)
    if secs <= 0:
        return None
    mix = run.mix
    nbytes = len(run.calls) * work.generate_lut(
        run.m, mix["batch"], mix["prompt_len"], mix["new_tokens"],
        run.table_bytes)
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / secs
