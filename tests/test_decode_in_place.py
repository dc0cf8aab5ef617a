"""The decode step writes only the new token's cache entries, in place.

Each layer reads its rows of the cache and attends over them and the
token's own key and value; the token's entries of every layer are
written at ``pos`` after the last layer, and ``generate()`` donates the
decode state, so a step copies no cache.  Checked here on the CPU:

* the dense step (``tests/bench/data/configs/tiny.json``, bf16 and int8
  caches) against a plain full-cache step written below, which writes
  each layer's token into that layer's slice and then attends over every
  position up to ``pos``: the same caches, logits within one bf16 step,
  the same greedy tokens;
* the latent step (``tiny-mla``) through ``generate()``, donated,
  against the same step jitted without donation: the same tokens;
* the lowered decode program of ``generate()`` marks the decode state,
  and nothing else, as donated;
* the gauge ``decode_cache_aliased_share`` on every ``generate()`` call.
"""
from __future__ import annotations

import importlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
DATA = ROOT / "tests" / "bench" / "data"
sys.path.insert(0, str(ROOT / "bench"))

from harness import model  # noqa: E402

from repro import obs  # noqa: E402
from repro.nn.attention import NEG_INF  # noqa: E402
from repro.nn.layers import embed_lookup, rms_norm  # noqa: E402
from repro.nn.mlp import mlp_block, project_logits  # noqa: E402
from repro.nn.rope import apply_rope  # noqa: E402
from repro.nn.transformer import _quantize_kv  # noqa: E402
from repro.serve.decode import decode_step  # noqa: E402
from repro.serve.kvcache import init_cache  # noqa: E402

gen = importlib.import_module("repro.serve.generate")

B, MAX_SEQ, STEPS = 2, 12, 8
SEED = 2 ** 31 + 19


@pytest.fixture(scope="module")
def tiny():
    conf = model.load_config("tiny", DATA)
    arch = model.load_architecture(conf, ROOT / "bench")
    return arch.arch_config(conf), arch.program_params(conf, SEED)


def _full_cache_step(params, cfg, cache, tokens, pos):
    """The plain full-cache decode step: every layer writes the token's
    key and value into its own slice of the cache (quantized, with its
    scales, for an int8 cache), then attends over positions ``0..pos``
    of that slice."""
    x = embed_lookup(params["embed"], tokens)
    cache = dict(cache)
    int8 = "k_scale" in cache
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    at = jnp.full((1,), pos)
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a, i=layer: a[i], params["blocks"])
        xin = rms_norm(x, p["ln1"], cfg.norm_eps)
        q = (xin @ p["wq"]).reshape(B, 1, h, dh)
        k = (xin @ p["wk"]).reshape(B, 1, kvh, dh)
        v = (xin @ p["wv"]).reshape(B, 1, kvh, dh)
        q = apply_rope(rms_norm(q, p["q_norm"], cfg.norm_eps), at,
                       cfg.rope_theta)
        k = apply_rope(rms_norm(k, p["k_norm"], cfg.norm_eps), at,
                       cfg.rope_theta)
        new = {"k": k, "v": v}
        if int8:
            (new["k"], new["k_scale"]), (new["v"], new["v_scale"]) = (
                _quantize_kv(k), _quantize_kv(v))
        for name, val in new.items():
            start = (layer, 0, pos) + (0,) * (val.ndim - 2)
            cache[name] = jax.lax.dynamic_update_slice(
                cache[name], val.astype(cache[name].dtype)[None], start)
        kc, vc = cache["k"][layer], cache["v"][layer]
        if int8:
            kc = kc.astype(q.dtype) * cache["k_scale"][layer][
                ..., None].astype(q.dtype)
            vc = vc.astype(q.dtype) * cache["v_scale"][layer][
                ..., None].astype(q.dtype)
        qg = q.reshape(B, kvh, h // kvh, dh)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        s = jnp.where(jnp.arange(MAX_SEQ) <= pos, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgt,btkd->bkgd", w.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        x = x + o.reshape(B, 1, h * dh) @ p["wo"]
        x = x + mlp_block(p, rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        # a layer's output leaves it in bf16, as each scanned layer's does
        x = jax.lax.optimization_barrier(x)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return project_logits(x, params["lm_head"], cfg, None), cache


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_dense_step_matches_the_full_cache_step(tiny, kv_dtype):
    cfg, params = tiny
    step = jax.jit(lambda p, c, t, i: decode_step(p, cfg, c, t, i))
    ref = jax.jit(lambda p, c, t, i: _full_cache_step(p, cfg, c, t, i))
    cache = ref_cache = init_cache(cfg, B, MAX_SEQ, kv_dtype=kv_dtype)
    tok = ref_tok = jnp.asarray([[3], [401]], jnp.int32)
    for i in range(STEPS):
        pos = jnp.int32(i)
        logits, cache = step(params, cache, tok, pos)
        ref_logits, ref_cache = ref(params, ref_cache, ref_tok, pos)
        for name in ref_cache:
            np.testing.assert_array_equal(
                np.asarray(cache[name].astype(jnp.float32)),
                np.asarray(ref_cache[name].astype(jnp.float32)),
                err_msg=f"{name} after step {i}")
        got = np.asarray(logits, np.float32)
        want = np.asarray(ref_logits, np.float32)
        # one bf16 step of the logits' scale: the new token's score joins
        # the softmax in another order, nothing else differs
        np.testing.assert_allclose(got, want, rtol=2 ** -7,
                                   atol=2 ** -7 * np.abs(want).max())
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        ref_tok = jnp.argmax(ref_logits[:, -1], -1).astype(jnp.int32)[:, None]
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))


def _tiny_mla():
    conf = model.load_config("tiny-mla", DATA / "tiny_mla")
    arch = model.load_architecture(conf, ROOT / "bench")
    tokens = np.random.default_rng(0).integers(
        1, arch.dims(conf)["V"], (B, 8)).astype(np.int32)
    return arch.arch_config(conf), arch.program_params(conf, SEED), tokens


def test_latent_generate_donates_and_serves_the_same_tokens():
    cfg, params, tokens = _tiny_mla()
    got = gen.generate(cfg, params, {"tokens": jnp.asarray(tokens)}, STEPS,
                       all_logits=True)
    # the same prefill, then the step jitted without donation
    logits, cache = jax.jit(lambda p, b: gen.prefill(
        p, cfg, b, max_seq=tokens.shape[1] + STEPS))(
        params, {"tokens": jnp.asarray(tokens)})
    step = jax.jit(lambda p, c, tk, pos: decode_step(p, cfg, c, tk, pos))
    want = []
    for i in range(STEPS):
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        want.append(np.asarray(tok))
        logits, cache = step(params, cache, tok,
                             np.int32(tokens.shape[1] + i))
    np.testing.assert_array_equal(got.tokens, np.concatenate(want, 1))
    np.testing.assert_array_equal(got.expert_counts,
                                  np.asarray(cache["expert_counts"]))


def test_generate_lowers_the_decode_state_donated(tiny, monkeypatch):
    """The decode program's StableHLO marks each leaf of the decode state
    as donated (``tf.aliasing_output`` where it is aliased to an output,
    ``jax.buffer_donor`` otherwise), and no other argument: the marks are
    the lowering's, whatever backend compiles it."""
    cfg, params = tiny
    texts = {}
    real = gen._program

    def keep_text(program, key, owner, lower):
        def lower_and_keep():
            lowered = lower()
            texts[program] = lowered.as_text()
            return lowered
        return real(program, None, owner, lower_and_keep)

    monkeypatch.setattr(gen, "_program", keep_text)
    tokens = jnp.ones((B, 4), jnp.int32)
    gen.generate(cfg, params, {"tokens": tokens}, 2)
    main = next(line for line in texts["decode"].splitlines()
                if "func.func public @main" in line)
    args = main.split("->")[0].split("%arg")[1:]
    donated = ["tf.aliasing_output" in a or "jax.buffer_donor" in a
               for a in args]
    n_params = len(jax.tree.leaves(params))
    n_state = len(jax.tree.leaves(init_cache(cfg, B, 6)))
    assert len(args) == n_params + n_state + 2      # + token, position
    assert donated == ([False] * n_params + [True] * n_state
                       + [False, False])
    assert "tf.aliasing_output" not in texts["prefill"]
    assert "jax.buffer_donor" not in texts["prefill"]


def test_generate_sets_the_aliased_share_on_every_call(tiny,
                                                        record_property):
    cfg, params = tiny
    batch = {"tokens": jnp.ones((B, 4), jnp.int32)}
    gen.clear_programs()
    seen = []
    for _ in range(2):            # a compile, then the kept program
        with obs.Telemetry() as tel:
            out = gen.generate(cfg, params, batch, 2)
        seen.append(tel.registry.snapshot().get(
            "decode_cache_aliased_share"))
    mem = out.decode_program.memory_analysis()
    reported = getattr(mem, "alias_size_in_bytes", None) is not None
    record_property("backend_reports_aliasing", reported)
    if reported:
        assert seen == [{"": 1.0}, {"": 1.0}], seen
    else:
        assert seen == [None, None], seen
