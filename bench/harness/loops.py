"""The measured window: offline calls back to back, or an open loop.

Both loops take the system already set up and warmed, run for the
window, and return plain records; nothing here computes a metric.
Compilations are counted by JAX's own monitoring events, so a program
that compiles inside the window shows in ``compiles``.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.monitoring

from . import system, traffic

# A compile request that the persistent cache answers still records the
# backend-compile event; "compiled" is the requests it did not answer.
# JAX's monitoring listeners are process-wide and cannot be removed, so
# one listener feeds one process-wide count that each CompileCounter
# reads the difference of.
_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "requests",
    "/jax/compilation_cache/cache_hits": "cache_loads",
    "/jax/core/compile/jaxpr_trace_duration": "traces",
}
_counts: collections.Counter = collections.Counter()
_listening = False


def _listen() -> None:
    global _listening
    if _listening:
        return
    _listening = True

    def on(name, *_, **__):
        if name in _COMPILE_EVENTS:
            _counts[_COMPILE_EVENTS[name]] += 1

    jax.monitoring.register_event_listener(on)
    jax.monitoring.register_event_duration_secs_listener(on)


class CompileCounter:
    """Counts compile-side events between ``start()`` and ``stop()``."""

    def __init__(self):
        _listen()
        self.counts = {}

    def start(self):
        self._at = dict(_counts)

    def stop(self) -> dict:
        c = {v: _counts[v] - self._at.get(v, 0)
             for v in _COMPILE_EVENTS.values()}
        self.counts = {"compiled": c["requests"] - c["cache_loads"],
                       "cache_loads": c["cache_loads"],
                       "traces": c["traces"]}
        return self.counts


# =========================================================================
# offline
# =========================================================================
@dataclasses.dataclass
class Call:
    index: int
    t0: float           # window clock
    t1: float
    tokens: object      # (B, new_tokens) served tokens
    prefill_s: float
    decode_s: float
    prompt_tokens: int
    new_tokens: int


def warm_offline(lut_cfg, params, tables, mix, vocab, seed):
    """One call at the cell's shapes: the prefill and decode programs are
    compiled, or read from the compile cache, and run once."""
    batch = traffic.offline_batch(mix, vocab, seed, call=-1)
    system.generate(lut_cfg, params, tables, batch, mix["new_tokens"])


def run_offline(lut_cfg, params, tables, mix, vocab, seed, seconds,
                on_start=None) -> tuple[list[Call], float]:
    """``generate()`` calls back to back until ``seconds`` have passed;
    the last call is the last one started before then.  Returns the
    calls and the window's length (first start to last end)."""
    calls: list[Call] = []
    if on_start:
        on_start()
    start = time.perf_counter()
    end = start
    with system.span("window"):
        while not calls or time.perf_counter() - start < seconds:
            i = len(calls)
            batch = traffic.offline_batch(mix, vocab, seed, i)
            t0 = time.perf_counter()
            gen = system.generate(lut_cfg, params, tables, batch,
                                  mix["new_tokens"])
            end = time.perf_counter()
            calls.append(Call(
                index=i, t0=t0 - start, t1=end - start, tokens=gen.tokens,
                prefill_s=gen.prefill_s, decode_s=gen.decode_s,
                prompt_tokens=int(batch["tokens"].size),
                new_tokens=int(gen.tokens.size)))
    return calls, end - start


# =========================================================================
# open loop
# =========================================================================
@dataclasses.dataclass
class Served:
    arrival: traffic.Arrival
    req: object = None          # the program's Request once submitted
    submit_s: float | None = None
    token_s: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.done


@dataclasses.dataclass
class OpenLoop:
    served: list                 # Served, one per arrival due in the window
    window_s: float              # the window's length (its last due time
                                 # lies inside it)
    drained_s: float             # window close to the last answer
    ticks: int                   # ticks run inside the window
    decode_calls: int            # decode-step calls inside the window
    late_s: list                 # submit time minus due time, per request


def _stamp(book: dict, now: float) -> None:
    """Give every token emitted since the last stamp the time ``now``."""
    for s in book.values():
        if s.req is not None:
            s.token_s.extend([now] * (len(s.req.out) - len(s.token_s)))


def warm_open_loop(bat: system.Batcher, mix: dict, arrivals, vocab: int):
    """Drive the batcher through every shape the window can use: one
    replay per distinct prompt length in ``arrivals``, and decode groups
    of every size from all slots down to one (16 requests of the
    shortest length finishing one tick apart)."""
    slots = mix["slots"]
    lengths = sorted({len(a.prompt) for a in arrivals})
    warm = [traffic.Arrival(rid=-1 - i, due_s=0.0, prompt=[1] * lengths[0],
                            max_new=2 + i) for i in range(slots)]
    warm += [traffic.Arrival(rid=-100 - i, due_s=0.0, prompt=[1] * n,
                             max_new=1) for i, n in enumerate(lengths)]
    for a in warm:
        bat.submit(a)
    while bat.busy:
        bat.tick()
    bat.b.finished.clear()
    jax.block_until_ready(bat.b.cache)


def run_open_loop(bat: system.Batcher, arrivals, seconds: float,
                  drain_s: float, on_start=None,
                  on_close=None) -> OpenLoop:
    """Submit each arrival once its due time has passed (between ticks),
    tick while there is work, close the window at ``seconds`` and drain
    what was due in it for at most ``drain_s`` more."""
    book = {a.rid: Served(a) for a in arrivals}
    order = list(arrivals)
    nxt = 0
    ticks = 0
    late = []
    calls0 = bat.decode_calls
    if on_start:
        on_start()
    start = time.perf_counter()
    clock = lambda: time.perf_counter() - start
    closed_at = None
    window = system.span("window")
    window.__enter__()
    while True:
        now = clock()
        while nxt < len(order) and order[nxt].due_s <= now:
            s = book[order[nxt].rid]
            s.req = bat.submit(order[nxt])
            s.submit_s = clock()
            late.append(s.submit_s - order[nxt].due_s)
            nxt += 1
        if closed_at is None and now >= seconds:
            closed_at = now
            calls_in_window = bat.decode_calls - calls0
            window.__exit__(None, None, None)
            if on_close:
                on_close()
        if closed_at is not None and (
                not bat.busy or now - closed_at >= drain_s):
            break
        if bat.busy:
            bat.tick()
            _stamp(book, clock())
            if closed_at is None:
                ticks += 1
        elif nxt < len(order):
            time.sleep(max(0.0, min(order[nxt].due_s - clock(),
                                    seconds - clock())))
        else:
            time.sleep(max(0.0, min(0.001, seconds - clock())))
    end = clock()
    return OpenLoop(
        served=[book[a.rid] for a in arrivals], window_s=closed_at,
        drained_s=end - closed_at, ticks=ticks,
        decode_calls=calls_in_window, late_s=late)
