"""Continuous-batching scheduler tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.nn import init_params
from repro.serve import decode_step, init_cache
from repro.serve.batching import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _greedy_reference(cfg, params, prompt, max_new, max_seq):
    """Single-request decode-only reference (same path the batcher uses)."""
    cache = init_cache(cfg, 1, max_seq)
    step = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t, pos))
    out = []
    tok = None
    for pos in range(len(prompt) + max_new - 1):
        t = prompt[pos] if pos < len(prompt) else out[-1]
        logits, cache = step(params, cache,
                             jnp.asarray([[t]], jnp.int32),
                             jnp.asarray(pos))
        nxt = int(jnp.argmax(logits[0, -1]))
        if pos >= len(prompt) - 1:
            out.append(nxt)
            if len(out) >= max_new:
                break
    return out


def test_batcher_completes_all_requests(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    b = ContinuousBatcher(cfg, params, batch_size=3, max_seq=48,
                          eos_token=-1)
    reqs = [
        Request(rid=i, prompt=list(rng.integers(1, cfg.vocab_size,
                                                4 + 3 * i)), max_new=4)
        for i in range(5)  # more requests than slots
    ]
    for r in reqs:
        b.submit(r)
    done = b.run()
    assert len(done) == 5
    assert all(len(r.out) == 4 for r in done)
    assert 0 < b.utilization <= 1.0


def test_slot_fills_to_max_seq(model):
    """Regression: a slot may decode until its position reaches max_seq
    (the last cache row is usable); eviction fires exactly at the cache
    boundary instead of one row early, and never lets a write get clamped
    out of bounds."""
    cfg, params = model
    rng = np.random.default_rng(2)
    max_seq = 8
    prompt = list(rng.integers(1, cfg.vocab_size, 5))
    b = ContinuousBatcher(cfg, params, batch_size=1, max_seq=max_seq,
                          eos_token=-1)
    b.submit(Request(rid=0, prompt=prompt, max_new=100))  # cache-bound
    done = b.run()
    assert len(done) == 1 and done[0].done
    # positions 0..max_seq-1 all written: len(prompt) prompt tokens plus
    # (max_seq - len(prompt)) decode writes; one output per write from the
    # final prompt position on.
    assert len(done[0].out) == max_seq - len(prompt) + 1
    assert all(s.req is None for s in b.slots)


def test_prompt_longer_than_cache_truncates(model):
    """A prompt that alone overflows the cache is truncated and evicted
    (previously the slot was never evicted and kept clamp-writing into the
    last row, corrupting other slots)."""
    cfg, params = model
    rng = np.random.default_rng(3)
    max_seq = 8
    long_prompt = list(rng.integers(1, cfg.vocab_size, max_seq + 4))
    short_prompt = list(rng.integers(1, cfg.vocab_size, 3))
    ref = _greedy_reference(cfg, params, short_prompt, 3, max_seq)

    b = ContinuousBatcher(cfg, params, batch_size=2, max_seq=max_seq,
                          eos_token=-1)
    b.submit(Request(rid=0, prompt=long_prompt, max_new=4))
    b.submit(Request(rid=1, prompt=short_prompt, max_new=3))
    done = sorted(b.run(), key=lambda r: r.rid)
    assert len(done) == 2
    assert done[0].done  # truncated, not stuck
    # the well-formed request is unaffected by its neighbor hitting the
    # cache boundary
    assert done[1].out == ref


def test_eos_eviction_and_slot_refill(model):
    """EOS evicts a request early and the freed slot picks up queued work."""
    cfg, params = model
    rng = np.random.default_rng(4)
    prompt = list(rng.integers(1, cfg.vocab_size, 4))
    # learn what the model will emit first, then declare it the EOS token
    probe = _greedy_reference(cfg, params, prompt, 1, 32)
    eos = probe[0]

    b = ContinuousBatcher(cfg, params, batch_size=1, max_seq=32,
                          eos_token=eos)
    b.submit(Request(rid=0, prompt=prompt, max_new=10))
    other = list(rng.integers(1, cfg.vocab_size, 3))
    b.submit(Request(rid=1, prompt=other, max_new=2))
    done = sorted(b.run(), key=lambda r: r.rid)
    assert len(done) == 2  # the single slot was refilled from the queue
    assert done[0].out == [eos]  # stopped at EOS, not at max_new
    assert len(done[1].out) == 2


def test_utilization_accounting(model):
    """utilization == active-slot work / (ticks * slots), exactly."""
    cfg, params = model
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg.vocab_size, 4)) for _ in range(2)]

    # both slots busy on every tick (same prompt length, same max_new)
    b = ContinuousBatcher(cfg, params, batch_size=2, max_seq=16,
                          eos_token=-1)
    for i, p in enumerate(prompts):
        b.submit(Request(rid=i, prompt=p, max_new=3))
    b.run()
    assert b.utilization == 1.0
    assert b.active_slot_steps == b.steps * 2

    # one busy slot of two => utilization 0.5
    b2 = ContinuousBatcher(cfg, params, batch_size=2, max_seq=16,
                           eos_token=-1)
    b2.submit(Request(rid=0, prompt=prompts[0], max_new=3))
    b2.run()
    assert b2.utilization == 0.5
    assert b2.active_slot_steps == b2.steps


def test_batcher_matches_single_request_decode(model):
    """Staggered multi-request batching must not change any request's
    greedy output (cache isolation across slots and positions)."""
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (3, 6, 5)]
    refs = [_greedy_reference(cfg, params, p, 3, 32) for p in prompts]

    b = ContinuousBatcher(cfg, params, batch_size=2, max_seq=32,
                          eos_token=-1)
    for i, p in enumerate(prompts):
        b.submit(Request(rid=i, prompt=p, max_new=3))
    done = sorted(b.run(), key=lambda r: r.rid)
    for r, want in zip(done, refs):
        assert r.out == want, (r.rid, r.out, want)


def test_empty_prompt_rejected_at_submit(model):
    cfg, params = model
    b = ContinuousBatcher(cfg, params, batch_size=1, max_seq=8,
                          eos_token=-1)
    with pytest.raises(ValueError, match="request 7: empty prompt"):
        b.submit(Request(rid=7, prompt=[], max_new=2))
    assert b.submitted == 0 and not b.queue


def test_stall_detection_names_stuck_request(model):
    """A request that can never be admitted (zero-slot pool) must raise
    naming its rid instead of spinning to max_ticks."""
    cfg, params = model
    b = ContinuousBatcher(cfg, params, batch_size=0, max_seq=8,
                          eos_token=-1)
    b.submit(Request(rid=42, prompt=[1, 2], max_new=2))
    with pytest.raises(RuntimeError, match=r"stalled.*\[42\]"):
        b.run(stall_ticks=3)


def test_metrics_accounting_and_slo(model):
    cfg, params = model
    rng = np.random.default_rng(3)
    b = ContinuousBatcher(cfg, params, batch_size=2, max_seq=16,
                          eos_token=-1)
    for i in range(3):
        b.submit(Request(rid=i, prompt=list(rng.integers(1, cfg.vocab_size,
                                                         4)),
                         max_new=3, slo_ms=0.001 if i == 0 else 1e9))
    b.run()
    m = b.metrics()
    assert m["submitted"] == m["finished"] == 3
    assert m["dropped"] == 0 and m["queued"] == 0 and m["active"] == 0
    assert m["latency_p50_s"] > 0 and m["latency_max_s"] >= m["latency_p50_s"]
    assert m["ttft_p50_s"] is not None
    # rid 0 carried an impossible 1us SLO, the others an absurdly lax one
    assert m["slo_tracked"] == 3 and m["slo_violations"] == 1


def test_metrics_zero_finished_requests(model):
    """Regression: metrics() on a batcher with nothing finished must
    return well-defined numbers — formatting the percentiles with :.3f
    used to TypeError on the None/empty-list cases."""
    cfg, params = model
    b = ContinuousBatcher(cfg, params, batch_size=2, max_seq=8,
                          eos_token=-1)
    m = b.metrics()
    assert m["submitted"] == m["finished"] == m["dropped"] == 0
    for key in ("latency_p50_s", "latency_p95_s", "latency_max_s",
                "ttft_p50_s", "utilization"):
        assert isinstance(m[key], float) and m[key] == m[key], key  # no NaN
        f"{m[key]:.3f}"  # formatting must not raise
    assert m["latency_p50_s"] == 0.0 and m["latency_max_s"] == 0.0
    assert m["slo_tracked"] == 0 and m["slo_violations"] == 0


def test_metrics_single_request_percentiles(model):
    """One finished request: every percentile is that request's latency
    (nearest-rank), not an interpolation artifact or an IndexError."""
    cfg, params = model
    rng = np.random.default_rng(6)
    b = ContinuousBatcher(cfg, params, batch_size=1, max_seq=16,
                          eos_token=-1)
    b.submit(Request(rid=0, prompt=list(rng.integers(1, cfg.vocab_size, 4)),
                     max_new=2))
    b.run()
    m = b.metrics()
    assert m["finished"] == 1
    assert m["latency_p50_s"] > 0.0
    assert m["latency_p50_s"] == m["latency_p95_s"] == m["latency_max_s"]
    assert m["ttft_p50_s"] > 0.0
