"""90th percentile over every request due in the window of the time from
its due time on the open-loop schedule to the end of the scheduler tick
that emitted its first token (host clock).  A request with no first
token counts as missing (infinite)."""
import math

from harness.readers import percentile


def read(run):
    if run.kind != "open_loop" or not run.loop.served:
        return None
    vals = [(s.token_s[0] - s.arrival.due_s) if s.token_s else math.inf
            for s in run.loop.served]
    return 1e3 * percentile(vals, 0.90)
