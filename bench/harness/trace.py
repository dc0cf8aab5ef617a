"""From a profiler trace to numbers: busy time, idle share, kernel time,
and the longest idle gaps named by what the host was doing.

Device operations are the events on the ``XLA Ops`` line of each
``/device:<platform>:<n>`` plane.  Busy time is the union of their
intervals, clipped to the window; the window is the benchmark's own
``bench:window`` span on the host.  A gap between device operations is
named by the innermost ``bench:`` span around its middle, and inside
that by the innermost other host event (the runtime's own, such as an
executable launch or a compile).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile

WINDOW_SPAN = "bench:window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str
    start: float        # seconds, trace clock
    end: float
    meta: str           # the event's name and string stats, for matching


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    ops: list           # per chip: list[Op] inside the window
    host: list          # (name, start, end) host events
    n_chips: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Union of device-op intervals, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(_union_len(o) for o in self.ops) / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_time(self, match) -> float:
        """Device seconds of ops that ``match(op)``, averaged over chips
        (nested ops counted once through their union)."""
        if not self.ops:
            return 0.0
        return sum(_union_len([o for o in ops if match(o)])
                   for ops in self.ops) / len(self.ops)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` ops with most self time (their time less that of the
        ops nested inside them, such as a loop's body), summed by short
        name and averaged over chips."""
        tot: dict = {}
        for ops in self.ops:
            for o, secs in _self_times(ops):
                key = short_name(o.name)
                tot[key] = tot.get(key, 0.0) + secs
        k = max(1, len(self.ops))
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / k] for name, secs in best]

    def gaps(self) -> list:
        """Idle intervals of chip 0 inside the window."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in _merged(self.ops[0] if self.ops else []):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def named_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps as ``[what the host did, s]``."""
        spans = [h for h in self.host if h[0].startswith("bench:")
                 and h[0] != WINDOW_SPAN]
        other = [h for h in self.host if not h[0].startswith("bench:")]
        out = []
        for s, e in sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (s + e)
            name = _innermost(spans, mid) or "bench:window"
            inner = _innermost(other, mid)
            out.append([f"{name} > {inner}" if inner else name, e - s])
        return out


def is_lut(op) -> bool:
    """A Pallas LUT kernel: each passes a ``name`` that starts with
    ``lut_``, which is its HLO instruction's name in the ``XLA Ops``
    events (``lut_act_stacked.3:tpu_custom_call``)."""
    return short_name(op.name).startswith("lut_")


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..), ...`` -> ``fusion.12``, with the
    custom-call target where there is one."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    mark = 'custom_call_target="'
    if mark in hlo:
        name += ":" + hlo.split(mark, 1)[1].split('"', 1)[0]
    return name


def _self_times(ops):
    """``(op, self seconds)`` for ops that nest on one line."""
    out, stack = [], []           # stack of [op, child seconds]

    def close(entry):
        op, child = entry
        out.append((op, max(0.0, op.end - op.start - child)))

    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and o.end > stack[-1][0].end:      # not inside it
            close(stack.pop())
        if stack:
            stack[-1][1] += o.end - o.start
        stack.append([o, 0.0])
    while stack:
        close(stack.pop())
    return out


def _innermost(events, t):
    best = None
    for name, s, e in events:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def _merged(ops):
    iv = sorted((o.start, o.end) for o in ops)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _union_len(ops) -> float:
    return sum(e - s for s, e in _merged(ops))


def _meta(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def reduce_file(path: str) -> Reduced:
    """Read one ``.xplane.pb`` into a :class:`Reduced`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([
                        Op(ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9, _meta(ev))
                        for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace {path} holds no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    clip = lambda ops: [dataclasses.replace(o, start=max(o.start, lo),
                                            end=min(o.end, hi))
                        for o in ops if o.end > lo and o.start < hi]
    return Reduced(window=(lo, hi), ops=[clip(o) for o in devices if o],
                   host=[h for h in host if h[2] > lo and h[1] < hi],
                   n_chips=len([o for o in devices if o]))


class Tracer:
    """Profiler session for the window of a ``--trace 1`` run, written
    under the run's temporary directory and deleted once reduced."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.running = False

    def start(self):
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.running = True

    def stop(self):
        if self.running:
            import jax

            jax.profiler.stop_trace()
            self.running = False

    def reduced_now(self) -> Reduced:
        self.stop()
        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        return reduce_file(sorted(files)[-1])

    def cleanup(self):
        self.stop()
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
