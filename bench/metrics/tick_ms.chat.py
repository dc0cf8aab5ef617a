"""The window's time over the scheduler ticks run in it (host clock)."""


def read(run):
    if run.kind != "open_loop" or not run.loop.ticks:
        return None
    return 1e3 * run.loop.window_s / run.loop.ticks
