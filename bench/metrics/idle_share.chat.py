"""1 - (union of device op intervals) / traced window (device trace)."""


def read(run):
    if run.kind != "open_loop" or run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()
