"""Calibration capture plus ReducedLUT compression in set-up (host
clock)."""


def read(run):
    return run.calib_s
