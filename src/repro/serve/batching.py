"""Continuous batching: slot-based request scheduler over decode steps.

The serving pattern real deployments use: a fixed pool of B slots shares
one jitted decode step; finished/empty slots are refilled with queued
requests (their prompts replayed through the shared cache at the slot's
positions), so the decode step never re-compiles and throughput stays at
the batch roofline regardless of request arrival order.

Offline-scale implementation of the scheduling logic (per-slot position
tracking, admission, eviction-on-EOS/length, utilization accounting) —
the part that is identical at cluster scale; the step function underneath
is the same one the 512-chip dry-run lowers.

Beyond scheduling:

* :meth:`run` detects no-progress ticks (a request that can never be
  admitted or advanced) and raises naming the stuck request instead of
  spinning to ``max_ticks``;
* per-request latency stamps (submit/first-token/done) feed
  :meth:`metrics` — dropped-request accounting, latency/TTFT
  percentiles, SLO violations.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.obs import drift as obs_drift

from .decode import decode_step, prefill_replay
from .kvcache import init_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    slo_ms: float | None = None    # per-request latency objective
    t_submit: float | None = None  # stamped by submit()
    t_first: float | None = None   # first output token
    t_done: float | None = None    # eviction

    @property
    def latency_s(self) -> float | None:
        if self.t_submit is None or self.t_done is None:
            return None
        return self.t_done - self.t_submit

    @property
    def ttft_s(self) -> float | None:
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0            # next cache position for this slot
    pending: list = None    # prompt tokens not yet ingested


class ContinuousBatcher:
    """Schedules requests over a fixed (B, max_seq) decode pool."""

    def __init__(self, cfg: ArchConfig, params, batch_size: int,
                 max_seq: int, eos_token: int = 0,
                 kv_dtype: str = "bfloat16", lut_tables: dict | None = None,
                 prefill: str = "step", mesh=None):
        if prefill not in ("step", "replay"):
            raise ValueError(
                f"prefill must be 'step' or 'replay', got {prefill!r}")
        self.cfg = cfg
        self.b = batch_size
        self.max_seq = max_seq
        self.eos = eos_token
        self.prefill = prefill
        self.mesh = mesh
        self.kv_dtype = kv_dtype
        self.lut_tables = lut_tables
        self.params = params
        self.cache = init_cache(cfg, batch_size, max_seq, kv_dtype)
        self._build_step_fns()
        self.slots = [_Slot() for _ in range(batch_size)]
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self.steps = 0
        self.active_slot_steps = 0
        self.replayed_tokens = 0
        self.submitted = 0

    def _build_step_fns(self) -> None:
        cfg = self.cfg
        if self.mesh is not None:
            # Sharded serving: data-parallel batch pool x (bit-exact)
            # tensor-parallel model, tables placed per the mesh policy.
            # The scheduler logic above this line is unchanged — slot
            # snapshots/restores run as eager ops on committed arrays and
            # keep their placement.
            from .sharded import ShardedServe

            self._serve = ShardedServe(cfg, self.mesh, self.lut_tables,
                                       kv_dtype=self.kv_dtype)
            self.lut_tables = self._serve.tables
            self.params = self._serve.place_params(self.params)
            self.cache = self._serve.place_cache(self.cache)
            self._replay = lambda p, c, toks: self._serve.replay(
                p, c, toks, 0)
            self._step = self._serve.decode
            self._step_plain = None
        else:
            self._serve = None
            tables = self.lut_tables
            # one wrapper; jit shape-specializes per prompt length
            # internally
            self._replay = jax.jit(lambda p, c, toks: prefill_replay(
                p, cfg, c, toks, 0, lut_tables=tables))
            # per-slot positions differ => decode_step takes a (B,) pos
            # vector?  the shared step uses a scalar pos; we instead track
            # per-slot pos and run the step with per-slot token + per-slot
            # position by vectorizing pos into the cache write via one
            # step per unique pos group — offline simplification: slots
            # advance in lock-step per step call with their own positions
            # through masked writes.
            self._step = jax.jit(
                lambda p, c, t, pos: decode_step(p, cfg, c, t, pos,
                                                 lut_tables=tables))

            # Sampled drift monitoring: when a DontCareMonitor is
            # active its callbacks are traced into self._step above.
            # This second jit of the SAME step traced under
            # suppressed() compiles the callback-free program; both
            # serve identical tokens (the monitor only observes), so
            # tick() may pick per step by sample_every.  Without a
            # monitor the closure is never called and never compiles.
            def _plain(p, c, t, pos):
                with obs_drift.suppressed():
                    return decode_step(p, cfg, c, t, pos,
                                       lut_tables=tables)

            self._step_plain = jax.jit(_plain)

    def _pick_step(self):
        """The jitted step for this tick: the monitored program on every
        ``sample_every``-th tick while a drift monitor is active, the
        plain program otherwise."""
        mon = obs_drift.current()
        if (mon is not None and self._step_plain is not None
                and self.steps % mon.sample_every != 0):
            return self._step_plain
        return self._step

    def submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError(
                f"request {req.rid}: empty prompt cannot be scheduled")
        req.t_submit = time.monotonic()
        self.submitted += 1
        self.queue.append(req)

    def _emit(self, req: Request, tok: int) -> None:
        req.out.append(tok)
        if req.t_first is None:
            req.t_first = time.monotonic()

    def _finish(self, slot: _Slot) -> None:
        req = slot.req
        req.done = True
        req.t_done = time.monotonic()
        self.finished.append(req)
        slot.req = None
        slot.pending = None
        t = obs.current()
        if t is not None:
            # Latency/TTFT land in registry histograms (the exportable
            # form) alongside the raw per-request stamps metrics() reads.
            if req.latency_s is not None:
                t.registry.histogram(
                    "serve_request_latency_s",
                    "submit-to-eviction request latency").observe(
                    req.latency_s)
            if req.ttft_s is not None:
                t.registry.histogram(
                    "serve_request_ttft_s",
                    "submit-to-first-token latency").observe(req.ttft_s)
            t.event("request_finish", rid=req.rid, tokens=len(req.out),
                    latency_s=(None if req.latency_s is None
                               else round(req.latency_s, 6)),
                    ttft_s=(None if req.ttft_s is None
                            else round(req.ttft_s, 6)))

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.req is None and self.queue:
                req = self.queue.popleft()
                slot.req = req
                slot.pos = 0
                slot.pending = list(req.prompt)
                if self.prefill == "replay" and len(slot.pending) > 1:
                    self._replay_slot(i, slot)

    def _replay_slot(self, i: int, slot: _Slot) -> None:
        """Batcher-level prefill replay: ingest an admitted slot's whole
        prompt through one compiled decode scan instead of one scheduler
        tick per token.

        Because the cache writes go through the decode write path, this
        fills int8 KV caches with exactly the quantized entries (values
        *and* scales) steady-state decode would produce, and evaluates the
        same LUT-compressed activations — the replay-vs-step outputs are
        asserted token-identical in tests/test_batching.py.  Prompts that
        alone overflow the cache mirror the step path: truncated to
        ``max_seq`` ingested tokens and evicted without an output token.
        """
        req = slot.req
        truncated = len(slot.pending) > self.max_seq
        toks = slot.pending[:self.max_seq]
        n = len(toks)
        with obs.span("prefill_replay", rid=req.rid, tokens=n):
            self._replay_slot_body(i, slot, req, truncated, toks, n)

    def _replay_slot_body(self, i, slot, req, truncated, toks, n) -> None:
        tokens = np.zeros((self.b, n), np.int32)
        tokens[i] = toks
        # The shared scan writes positions [0, n) for EVERY row; rows of
        # other slots must keep their entries — snapshot and restore.
        others = [j for j in range(self.b) if j != i]
        snap = {name: self.cache[name][:, others, :n]
                for name in self.cache if name in
                ("k", "v", "k_scale", "v_scale")}
        logits, self.cache = self._replay(
            self.params, self.cache, jnp.asarray(tokens))
        if others:
            oth = jnp.asarray(others)
            for name, before in snap.items():
                self.cache[name] = self.cache[name].at[:, oth, :n].set(
                    before)
        slot.pos = n
        slot.pending = []
        self.replayed_tokens += n
        if truncated:
            # step-path semantics: the prompt never finished ingesting, so
            # no output token is produced; the slot is evicted at the
            # cache boundary.
            self._finish(slot)
            return
        self._emit(req, int(jnp.argmax(logits[i, -1])))
        if (slot.pos >= self.max_seq or len(req.out) >= req.max_new
                or req.out[-1] == self.eos):
            self._finish(slot)

    @property
    def n_active(self) -> int:
        return sum(1 for s in self.slots if s.req is not None)

    def step(self) -> None:
        """One scheduler tick: each active slot ingests its next pending
        prompt token or decodes one new token.  Tick telemetry (queue
        depth, slot utilization, tick duration) is recorded per tick in
        the registry and as *sampled* timeline events — ``--obs-sample``
        thins the per-tick records, never the gauges/counters."""
        t = obs.current()
        t0 = time.monotonic() if t is not None else 0.0
        self._admit()
        if self.n_active == 0:
            return
        # assemble the per-slot token vector
        tokens = np.zeros((self.b, 1), np.int32)
        for i, slot in enumerate(self.slots):
            if slot.req is None:
                continue
            if slot.pending:
                tokens[i, 0] = slot.pending[0]
            elif slot.req.out:
                tokens[i, 0] = slot.req.out[-1]
            else:
                tokens[i, 0] = slot.req.prompt[-1]
        # all slots share the step; positions tracked per slot — offline
        # the pool advances with a common position counter per slot via
        # sequential sub-steps grouped by position (simplest correct form:
        # one call per distinct position value)
        by_pos: dict[int, list[int]] = {}
        for i, slot in enumerate(self.slots):
            if slot.req is not None:
                by_pos.setdefault(slot.pos, []).append(i)
        for pos, idxs in sorted(by_pos.items()):
            # A slot is evicted the moment its position reaches max_seq, so
            # every write lands strictly inside the cache.  Without this,
            # JAX clamps an out-of-range cache write index to the last row,
            # silently corrupting position max_seq-1 for other requests.
            assert pos < self.max_seq, (
                f"slot position {pos} out of cache bounds "
                f"(max_seq={self.max_seq}); eviction failed to fire")
            # the shared step writes cache index `pos` for EVERY row; rows
            # outside this position group must keep their entry — snapshot
            # the (L, B, KV, D) slice and restore the other rows after.
            others = [i for i in range(self.b) if i not in idxs]
            snap = {name: self.cache[name][:, :, pos]
                    for name in self.cache if name in
                    ("k", "v", "k_scale", "v_scale")}
            logits, self.cache = self._pick_step()(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(pos))
            if others:
                oth = jnp.asarray(others)
                for name, before in snap.items():
                    self.cache[name] = self.cache[name].at[:, oth, pos].set(
                        before[:, oth])
            nxt = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)
            for i in idxs:
                slot = self.slots[i]
                req = slot.req
                slot.pos += 1
                self.active_slot_steps += 1
                if slot.pending:
                    slot.pending.pop(0)
                    if not slot.pending:  # prompt done: first output token
                        self._emit(req, int(nxt[i]))
                else:
                    self._emit(req, int(nxt[i]))
                # Evict when finished (max_new / EOS) or when the cache is
                # exactly full: ``slot.pos`` is the *next* write index, so
                # the slot may keep decoding until pos == max_seq — the
                # last row (max_seq - 1) is usable, and a slot whose prompt
                # alone fills the cache is truncated rather than allowed to
                # write out of bounds.
                if (slot.pos >= self.max_seq
                        or (not slot.pending
                            and (len(req.out) >= req.max_new
                                 or req.out[-1] == self.eos))):
                    self._finish(slot)
        self.steps += 1
        if t is not None:
            r = t.registry
            r.counter("batcher_ticks_total").inc()
            r.gauge("batcher_queue_depth").set(len(self.queue))
            r.gauge("batcher_active_slots").set(self.n_active)
            r.gauge("batcher_slot_utilization").set(self.utilization)
            r.histogram("batcher_tick_s", "scheduler tick duration"
                        ).observe(time.monotonic() - t0)
            t.event("tick", sampled=True, tick=self.steps,
                    queued=len(self.queue), active=self.n_active,
                    dur_s=round(time.monotonic() - t0, 6))

    def run(self, max_ticks: int = 10000,
            stall_ticks: int = 4) -> list[Request]:
        """Drive the scheduler until the queue drains (or ``max_ticks``).

        A tick that neither finishes a request, advances a slot, nor
        replays prompt tokens makes no progress; ``stall_ticks``
        consecutive ones mean some request can never be admitted or
        advanced (e.g. a zero-slot pool) — raise naming it instead of
        spinning to ``max_ticks``."""
        stalled = 0
        while (self.queue or self.n_active) and self.steps < max_ticks:
            before = (len(self.finished), self.active_slot_steps,
                      self.replayed_tokens)
            self.step()
            after = (len(self.finished), self.active_slot_steps,
                     self.replayed_tokens)
            stalled = stalled + 1 if after == before else 0
            if stalled >= stall_ticks:
                stuck = sorted(
                    [s.req.rid for s in self.slots if s.req is not None]
                    + [r.rid for r in self.queue])
                raise RuntimeError(
                    f"ContinuousBatcher stalled: no progress for "
                    f"{stalled} consecutive ticks with request id(s) "
                    f"{stuck} still unserved (batch_size={self.b}, "
                    f"max_seq={self.max_seq}) — the scheduler can never "
                    f"admit or advance them")
        return self.finished

    @property
    def utilization(self) -> float:
        """Mean fraction of slots doing useful work per tick."""
        if self.steps == 0:
            return 0.0
        return self.active_slot_steps / (self.steps * self.b)

    def metrics(self) -> dict:
        """Observability snapshot: request accounting (anything
        submitted but neither finished, queued, nor in-flight counts as
        dropped),
        latency/TTFT percentiles over finished requests, and SLO
        violations for requests that carried a target."""
        lats = sorted(r.latency_s for r in self.finished
                      if r.latency_s is not None)
        ttfts = sorted(r.ttft_s for r in self.finished
                       if r.ttft_s is not None)

        def pct(xs: list, q: float) -> float:
            # Nearest-rank percentile, total on both edge cases: no
            # finished requests -> 0.0 (the snapshot must still format
            # and export), one request -> that request at every q.
            if not xs:
                return 0.0
            rank = min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))
            return float(xs[rank])

        slo = [r for r in self.finished if r.slo_ms is not None
               and r.latency_s is not None]
        return {
            "submitted": self.submitted,
            "finished": len(self.finished),
            "queued": len(self.queue),
            "active": self.n_active,
            "dropped": (self.submitted - len(self.finished)
                        - len(self.queue) - self.n_active),
            "ticks": self.steps,
            "utilization": self.utilization,
            "replayed_tokens": self.replayed_tokens,
            "latency_p50_s": pct(lats, 0.50),
            "latency_p95_s": pct(lats, 0.95),
            "latency_max_s": float(lats[-1]) if lats else 0.0,
            "ttft_p50_s": pct(ttfts, 0.50),
            "slo_violations": sum(
                1 for r in slo if r.latency_s * 1e3 > r.slo_ms),
            "slo_tracked": len(slo),
        }
