"""Greedy generation: one prefill, then a greedy decode loop.

The one serving loop that ``repro.launch.serve``, the backend-equivalence
harness (:func:`repro.serve.plans.verify_backend_equivalence`) and
``chip_smoke.py`` share, single-device or through a
:class:`~repro.serve.sharded.ShardedServe`.

The compiled programs are kept across calls, in a bounded process-wide
cache (:func:`clear_programs` empties it): a call whose programs were
built before runs them with no tracing, lowering or compile-cache load.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.calib.capture import capture_active
from repro.obs.drift import monitor_active

from .decode import decode_step, prefill, prefill_replay
from .kvcache import init_cache

MAX_PROGRAMS = 8


class _Programs:
    """Compiled programs by key, least recently used evicted first.

    Each entry holds its executable and the tables or ``ShardedServe``
    object whose ``id`` is in its key, so that ``id`` cannot name
    another object while the entry lives; never weights, a batch or a
    cache.
    """

    def __init__(self, bound: int):
        self.bound = bound
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            return entry[0]

    def put(self, key, exe, owner) -> None:
        with self._lock:
            self._entries[key] = (exe, owner)
            self._entries.move_to_end(key)
            while len(self._entries) > self.bound:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __contains__(self, key) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)


_PROGRAMS = _Programs(MAX_PROGRAMS)


def clear_programs() -> None:
    """Drop every kept program: the next call of each shape builds anew.
    For callers that change a tables dict in place or flip a module
    switch of :mod:`repro.nn`."""
    _PROGRAMS.clear()


def _hook_entered() -> bool:
    """True while a trace-time hook is entered (an activation capture or
    a don't-care monitor): a program traced then calls into the hook, so
    it is built for that call alone and not kept."""
    return capture_active() or monitor_active()


def _signature(*args) -> tuple:
    """Tree structure, and type (shape, dtype, weak type) and placement
    of every leaf: what a compiled program is specialised to."""
    leaves, tree = jax.tree.flatten(args)
    return tree, tuple((jax.typeof(x), getattr(x, "sharding", None))
                       for x in leaves)


@dataclasses.dataclass
class Generation:
    """One greedy prefill + decode run and where its time went.

    Compile and run times are separate: each program is compiled ahead of
    time (``lower().compile()``), or taken from the kept programs (the
    compile times are then the lookup's), and every run time ends in
    ``block_until_ready``.
    """

    tokens: np.ndarray          # (B, new_tokens) int32 greedy tokens
    # float32 (B, V) logits at the last position: the prefill's, then each
    # kept decode step's (the first one, or all with ``all_logits``)
    logits: list
    prefill_compile_s: float
    prefill_s: float
    decode_compile_s: float
    decode_s: float             # all new_tokens decode steps
    decode_program: object      # the compiled decode step (``as_text()``)
    # (E_held + 1,) routed (token, expert) pairs of the call, summed over
    # the expert layers: one per held expert, then those held elsewhere
    # (``None`` for a model without held experts)
    expert_counts: np.ndarray | None = None

    @property
    def prefill_logits(self) -> np.ndarray:
        return self.logits[0]

    @property
    def step_logits(self) -> np.ndarray | None:
        """Logits of the first decode step (``None`` with no steps)."""
        return self.logits[1] if len(self.logits) > 1 else None


def _program(program: str, key, owner, lower):
    """The compiled ``program`` kept under ``key``, or ``lower()``
    compiled now and kept (``key`` None: for this call alone).  Returns
    the executable and its compile seconds: the compile's, or on a hit
    the lookup's (both spans)."""
    hit = key is not None and key in _PROGRAMS
    obs.count("generate_programs_total", program=program,
              outcome="hit" if hit else "miss")
    cached = {"cached": True} if hit else {}
    t0 = time.perf_counter()
    with obs.span(f"lower.{program}", **cached):
        exe = _PROGRAMS.get(key) if hit else None
        lowered = lower() if exe is None else None
    if lowered is not None:
        t0 = time.perf_counter()
    with obs.span(f"compile.{program}", **cached):
        if lowered is not None:
            exe = lowered.compile()
    compile_s = time.perf_counter() - t0
    if lowered is not None and key is not None:
        _PROGRAMS.put(key, exe, owner)
    return exe, compile_s


def generate(cfg, params, batch: dict, new_tokens: int, *,
             lut_tables: dict | None = None, serve=None,
             kv_int8: bool = False, max_seq: int | None = None,
             all_logits: bool = False) -> Generation:
    """Greedy-serve ``batch``: prefill, then ``new_tokens`` decode steps.

    Single-device with ``lut_tables`` closed over, or through ``serve``
    (a :class:`~repro.serve.ShardedServe` holding its placed tables).
    With ``kv_int8`` the prefill cache is re-homed into an int8 cache by
    replaying the prompt through the quantizing decode write path; the
    replay counts as prefill time.  ``max_seq`` defaults to the prompt
    (patch prefix included) plus ``new_tokens``.  ``all_logits`` keeps
    every decode step's logits, not only the first one's.

    The single-device decode program takes the decode state donated:
    each step's state is used once, so the step writes the token's cache
    entries in place instead of copying the cache.  The share of the
    state's bytes it aliases is the gauge ``decode_cache_aliased_share``,
    set on every call under an entered ``Telemetry``.

    The compiled programs (prefill, decode, and the ``kv_int8`` replay)
    are kept across calls, each under its program and traced function,
    ``cfg``, ``max_seq``, the identity of ``lut_tables`` (or of
    ``serve``) and the arguments' types and placements; nothing is kept
    while a trace-time hook is entered (:func:`_hook_entered`).  A call
    that matches runs the kept executables; counted as
    ``generate_programs_total`` (``program``, ``outcome`` ``hit`` or
    ``miss``) under an entered ``Telemetry``.  The module switches of
    :mod:`repro.nn` (``FAST_STREAM``, ``SEQ_PARALLEL``, ``WKV_CHUNK``)
    are not in the key: a caller that flips one between calls clears
    the kept programs first (:func:`clear_programs`).

    The host work is in ``repro.obs`` spans (profiler annotations named
    ``repro:<span>``): ``generate`` around the call; ``lower.prefill``,
    ``lower.decode`` (tracing and lowering each program) and
    ``compile.prefill``, ``compile.decode`` (``Lowered.compile()``: a
    compile, or a persistent-cache load), both only a lookup and marked
    ``cached=True`` where the program was kept; ``prefill``, ``decode``;
    and ``readback`` (tokens and kept logits to the host, and a held
    expert layer's assignment counts, which the decode state carries and
    which go to the ``moe_assignments_total`` counter).
    """
    tokens = batch["tokens"]
    b, t = tokens.shape
    with obs.span("generate", batch=b, prompt_len=t,
                  new_tokens=new_tokens):
        if cfg.family == "vlm" and "patches" in batch:
            t += batch["patches"].shape[1]  # the patch prefix fills the cache
        max_seq = max_seq or t + new_tokens
        keep = not _hook_entered()
        owner = lut_tables if serve is None else serve

        def key(program, fn, *args):
            if not keep:
                return None
            return (program, fn, cfg, max_seq, id(owner), _signature(*args))

        def lower_prefill():
            if serve is not None:
                return serve.lower_prefill(params, batch, max_seq)
            return jax.jit(lambda p, x: prefill(
                p, cfg, x, max_seq=max_seq,
                lut_tables=lut_tables)).lower(params, batch)

        extra = () if serve is None else (serve.table_operands,)
        pre_exe, pre_compile_s = _program(
            "prefill", key("prefill", prefill, params, batch), owner,
            lower_prefill)
        t0 = time.perf_counter()
        with obs.span("prefill", batch=b, prompt_len=t):
            logits, cache = jax.block_until_ready(
                pre_exe(params, batch, *extra))
            if kv_int8 and cfg.family in ("dense", "moe", "vlm"):
                cache_q = init_cache(cfg, b, max_seq, kv_dtype="int8")
                if serve is not None:
                    cache_q = serve.place_cache(cache_q)
                    logits, cache = serve.replay(params, cache_q, tokens)
                else:
                    def lower_replay():
                        return jax.jit(lambda p, c, tk: prefill_replay(
                            p, cfg, c, tk, 0, lut_tables=lut_tables)).lower(
                            params, cache_q, tokens)

                    rep_exe, _ = _program(
                        "replay",
                        key("replay", prefill_replay, params, cache_q, tokens),
                        owner, lower_replay)
                    logits, cache = rep_exe(params, cache_q, tokens)
                jax.block_until_ready(cache)
        pre_s = time.perf_counter() - t0
        kept = [logits[:, -1]]

        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        pos0 = np.int32(t)

        def lower_decode():
            if serve is not None:
                return serve.lower_decode(params, cache, tok, pos0)
            return jax.jit(lambda p, c, tk, pos: decode_step(
                p, cfg, c, tk, pos, lut_tables=lut_tables),
                donate_argnums=1).lower(params, cache, tok, pos0)

        dec_exe, dec_compile_s = _program(
            "decode", key("decode", decode_step, params, cache, tok, pos0),
            owner, lower_decode)
        if serve is None:
            _gauge_aliased_share(dec_exe, cache)
        outs = []
        t0 = time.perf_counter()
        with obs.span("decode", batch=b, new_tokens=new_tokens):
            for i in range(new_tokens):
                outs.append(tok)
                logits, cache = dec_exe(params, cache, tok, np.int32(t + i),
                                        *extra)
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
                if i == 0 or all_logits:
                    kept.append(logits[:, -1])
            jax.block_until_ready((tok, cache))
        dec_s = time.perf_counter() - t0
        with obs.span("readback"):
            toks = (np.concatenate([np.asarray(o) for o in outs], axis=1)
                    if outs else np.zeros((b, 0), np.int32))
            kept = [np.asarray(lg, np.float32) for lg in kept]
            counts = (np.asarray(cache["expert_counts"])
                      if isinstance(cache, dict) and "expert_counts" in cache
                      else None)
        if counts is not None:
            _count_assignments(counts)
        return Generation(
            tokens=toks, logits=kept,
            prefill_compile_s=pre_compile_s, prefill_s=pre_s,
            decode_compile_s=dec_compile_s, decode_s=dec_s,
            decode_program=dec_exe, expert_counts=counts)


def _gauge_aliased_share(exe, cache) -> None:
    """Set ``decode_cache_aliased_share``: the share of the decode state's
    bytes on the device (tile padding included, as the compiler counts
    them) that the compiled decode program writes in place of its input;
    1.0 when the donated state is aliased whole.  Left unset where the
    backend reports no memory analysis."""
    mem = exe.memory_analysis()
    alias = getattr(mem, "alias_size_in_bytes", None)
    if alias is None:
        return
    nbytes = sum(x.on_device_size_in_bytes() for x in jax.tree.leaves(cache))
    obs.gauge("decode_cache_aliased_share", alias / nbytes,
              help="share of the decode state's bytes the decode program "
                   "aliases")


def _count_assignments(counts: np.ndarray) -> None:
    """Add one call's routed pairs to ``moe_assignments_total``, labelled
    by held expert (``"0"``..) or ``"elsewhere"``."""
    names = [str(e) for e in range(len(counts) - 1)] + ["elsewhere"]
    for name, n in zip(names, counts.tolist()):
        obs.count("moe_assignments_total", float(n),
                  help="routed (token, expert) pairs, summed over the "
                       "expert layers", expert=name)
