"""95th percentile of every gap between successive output tokens of the
requests due in the window; a token's time is the end of the scheduler
tick that emitted it (host clock)."""
from harness.readers import percentile


def read(run):
    if run.kind != "open_loop":
        return None
    gaps = [b - a for s in run.loop.served
            for a, b in zip(s.token_s, s.token_s[1:])]
    return 1e3 * percentile(gaps, 0.95) if gaps else None
