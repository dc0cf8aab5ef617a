"""Routing skew over the experts this chip holds: the most assignments
any held expert got in the window over the held experts' mean, from the
program's ``moe_assignments_total`` counter (labels ``expert="0"``..,
and ``"elsewhere"`` for experts held on other chips, which is left out).
1.0 is an even load; the slowest expert's share of the expert layer's
work grows with it (program counter)."""
import re

LABEL = re.compile(r'expert="(\d+)"')


def read(run):
    if run.kind != "offline":
        return None
    held = [v for k, v in run.counters.get("moe_assignments_total",
                                            {}).items()
            if LABEL.search(k)]
    if not held or sum(held) <= 0:
        return None
    return max(held) / (sum(held) / len(held))
