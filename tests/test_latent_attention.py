"""DeepSeek-V2's mechanisms against the plain reference, on the CPU at a
miniature of the same structure: one dense layer and two expert layers,
latent rank 32, 8 rotary dims, 16 nope and value dims a head, 16 routed
experts of which 4 are held here, top 3, one shared expert
(``tests/bench/data/tiny_mla/configs/tiny-mla.json``).

The program computes in float32 here (weights drawn in bf16 by the
benchmark's generator, then widened), so its distance from the float32
reference is rounding alone; the tolerance below is set from that, and
the same comparison fails for the reference with fp8 projections and for
the program computing in bf16, one precision step down."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from harness import check, model  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.base import YarnScaling  # noqa: E402
from repro.nn import rope  # noqa: E402
from repro.nn.moe import moe_ffn_held  # noqa: E402
from repro.serve.decode import decode_step, prefill  # noqa: E402
from repro.serve.generate import generate  # noqa: E402

CONF = model.load_config("tiny-mla",
                         ROOT / "tests" / "bench" / "data" / "tiny_mla")
ARCH = model.load_architecture(CONF, ROOT / "bench")
REF = model.load_reference(CONF["reference"], ROOT / "bench")
M = ARCH.dims(CONF)
SEED = 2 ** 31 + 16
# float32 program against the float32 reference: the rounding of float32
# sums taken in another order (absorbed decode, chunked attention); read
# 3.0e-6 here, against 0.069 for the program in bf16 and 0.80 for the
# reference with fp8 projections, on logits up to 3.7
TOL = 1e-3


def _program(dtype):
    cfg = dataclasses.replace(ARCH.arch_config(CONF), dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          ARCH.program_params(CONF, SEED))
    return cfg, params


def _tokens(b=2, t=12, extra=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, M["V"], (b, t + extra)).astype(np.int32)


def _served_logits(cfg, params, full, t):
    """Prefill ``full[:, :t]``, then decode ``full[:, t:]`` teacher-forced:
    float32 logits (B, K, V) at positions t-1 .. T-2."""
    logits, cache = jax.jit(lambda p, b: prefill(
        p, cfg, b, max_seq=full.shape[1]))(params, {"tokens": full[:, :t]})
    step = jax.jit(lambda p, c, tk, pos: decode_step(p, cfg, c, tk, pos))
    outs = [logits]
    for i in range(full.shape[1] - t - 1):
        lg, cache = step(params, cache, full[:, t + i:t + i + 1],
                         jnp.int32(t + i))
        outs.append(lg)
    return np.asarray(jnp.concatenate(outs, 1), np.float32)


@pytest.fixture(scope="module")
def reference():
    full = _tokens()
    t = 12
    pos = np.broadcast_to(np.arange(t - 1, full.shape[1] - 1),
                          (full.shape[0], full.shape[1] - t))
    w = check.reference_weights(ARCH, M, SEED)
    ref = REF.logits_at(M, w, full, pos.astype(np.int32))
    ctl = REF.logits_at(M, w, full, pos.astype(np.int32), quant="fp8")
    return full, t, ref, ctl


def test_prefill_then_latent_decode_match_the_reference(reference):
    full, t, ref, ctl = reference
    got = _served_logits(*_program("float32"), full, t)
    assert np.max(np.abs(got - ref)) < TOL
    # the comparison is tight enough to see one precision step down
    assert np.max(np.abs(ctl - ref)) > TOL
    low = _served_logits(*_program("bfloat16"), full, t)
    assert np.max(np.abs(low - ref)) > TOL


def test_absorbed_decode_equals_expanded_attention():
    from repro.nn.transformer import _mla_attn, _mla_decode

    cfg, params = _program("float32")
    p = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, M["d"]), jnp.float32)
    want, (c_kv, k_pe) = _mla_attn(p, x, cfg)
    pos = 8
    got, (c_new, pe_new) = _mla_decode(
        p, x[:, pos:pos + 1], cfg, c_kv.astype(jnp.float32),
        k_pe.astype(jnp.float32), jnp.int32(pos))
    np.testing.assert_allclose(got[:, 0], want[:, pos], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c_new[:, 0], c_kv[:, pos], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(pe_new[:, 0], k_pe[:, pos], rtol=1e-6,
                               atol=1e-6)


def test_yarn_at_the_published_sizes():
    yarn = YarnScaling(factor=40, original_max_position_embeddings=4096,
                       beta_fast=32, beta_slow=1, mscale=0.707,
                       mscale_all_dim=0.707)
    assert rope.yarn_correction_range(64, 10000.0, yarn) == (10, 23)
    m = rope.yarn_mscale(40, 0.707)
    assert m == pytest.approx(1.26080, abs=5e-6)
    assert rope.yarn_cos_sin_scale(yarn) == 1.0
    assert rope.yarn_attention_scale(192, yarn) == pytest.approx(
        192 ** -0.5 * m * m)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra * mask + extra / 40 * (1 - mask)
    np.testing.assert_allclose(rope.yarn_freqs(64, 10000.0, yarn), want,
                               rtol=1e-6)
    # and the reference's own formula agrees
    conf = json.loads(json.dumps(CONF))
    conf["qk_rope_head_dim"] = 64
    np.testing.assert_allclose(REF.yarn_inv_freq(ARCH.dims(conf)), want,
                               rtol=1e-6)


# -------------------------------------------------------------------------
# the held expert layer
# -------------------------------------------------------------------------
E, K, D, F = 16, 3, 32, 8


def _experts(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (40, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, E), jnp.float32) * D ** -0.5
    w_in = jax.random.normal(ks[2], (E, D, 2 * F), jnp.float32) * D ** -0.5
    w_out = jax.random.normal(ks[3], (E, F, D), jnp.float32) * F ** -0.5
    return x, router, w_in, w_out


def _plain_layer(x, router, w_in, w_out, experts, norm):
    """Every expert in ``experts`` on every token, weighted by its gate
    (zero where the router did not pick it)."""
    probs = jax.nn.softmax(x @ router, -1)
    top_p, top_i = jax.lax.top_k(probs, K)
    if norm:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, E) * top_p[..., None], -2)
    y = jnp.zeros_like(x)
    for e in experts:
        h = x @ w_in[e]
        y = y + gate[:, e:e + 1] * ((jax.nn.silu(h[:, :F]) * h[:, F:])
                                    @ w_out[e])
    return y


@pytest.mark.parametrize("norm", [False, True])
def test_the_shares_add_up_to_the_uncut_layer(norm):
    x, router, w_in, w_out = _experts()
    shared = jax.random.normal(jax.random.PRNGKey(9), (D, D)) * 0.1
    whole = _plain_layer(x, router, w_in, w_out, range(E), norm) + x @ shared
    parts = x @ shared          # what every chip computes alike, once
    total = 0
    for first in range(0, E, 4):
        y, counts = moe_ffn_held(
            x, router, w_in[first:first + 4], w_out[first:first + 4],
            top_k=K, first_expert=first, norm_topk_prob=norm, tile=8)
        parts = parts + y
        total += int(counts[:4].sum())
        assert int(counts.sum()) == x.shape[0] * K
    assert total == x.shape[0] * K
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)


def _with_bias(x, router, bias):
    """A constant input feature whose router row is ``bias``: experts
    with a large bias lead for every token."""
    xb = jnp.concatenate([x, jnp.ones((x.shape[0], 1))], -1)
    return xb, jnp.concatenate([router, bias[None]], 0)


def test_dropless_when_every_token_picks_the_same_held_experts():
    x, router, w_in, w_out = _experts(1)
    # experts 0, 1, 2 lead for every token: 40 pairs each, 5 tiles of 8
    xb, rb = _with_bias(x, router * 0.1,
                        jnp.zeros(E).at[:3].set(jnp.array([50., 40., 30.])))
    w_in = jnp.pad(w_in, ((0, 0), (0, 1), (0, 0)))
    w_out = jnp.pad(w_out, ((0, 0), (0, 0), (0, 1)))
    y, counts = moe_ffn_held(xb, rb, w_in[:4], w_out[:4], top_k=K,
                             first_expert=0, norm_topk_prob=False, tile=8)
    assert counts.tolist() == [40, 40, 40, 0, 0]
    np.testing.assert_allclose(
        y, _plain_layer(xb, rb, w_in, w_out, range(4), False),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob_false_keeps_the_softmax_weights(norm):
    x, router, _, _ = _experts(2)
    xb, rb = _with_bias(x, router, jnp.zeros(E))
    # every expert reads 1 in column 0 (silu(40) / 40 ~ 1), so the
    # layer's column 0 is the sum of the picked experts' weights
    w_in = jnp.zeros((E, D + 1, 2 * F)).at[:, D, 0].set(40.0)
    w_in = w_in.at[:, D, F].set(1 / 40)
    w_out = jnp.zeros((E, F, D + 1)).at[:, 0, 0].set(1.0)
    y, _ = moe_ffn_held(xb, rb, w_in, w_out, top_k=K, norm_topk_prob=norm)
    top_p = jax.lax.top_k(jax.nn.softmax(xb @ rb, -1), K)[0]
    want = jnp.ones(x.shape[0]) if norm else top_p.sum(-1)
    np.testing.assert_allclose(y[:, 0], want, rtol=1e-4)
    assert norm or float(want.max()) < 0.99


def test_generate_counts_the_routed_pairs():
    cfg, params = _program("float32")
    full = _tokens(b=2, t=8, extra=0)
    tel = obs.Telemetry()
    with tel:
        gen = generate(cfg, params, {"tokens": jnp.asarray(full)}, 5)
    n_moe = M["L"] - M["dense"]
    pairs = n_moe * M["k"] * 2 * (8 + 5)
    assert gen.expert_counts.shape == (M["held"] + 1,)
    assert int(gen.expert_counts.sum()) == pairs
    counted = tel.registry.snapshot()["moe_assignments_total"]
    assert sum(counted.values()) == pairs
    assert counted['{expert="elsewhere"}'] == gen.expert_counts[-1]


def test_the_preset_is_the_published_model():
    from repro.configs.deepseek_v2_lite import CONFIG as c
    from repro.serve.kvcache import cache_specs

    assert (c.n_layers, c.d_model, c.n_heads, c.vocab_size, c.first_k_dense,
            c.d_ff_dense) == (27, 2048, 16, 102400, 1, 10944)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.d_expert, c.moe.n_shared,
            c.moe.held, c.moe.norm_topk_prob) == (64, 6, 1408, 2, 64, False)
    assert c.n_params() == pytest.approx(15.7e9, rel=0.01)
    spec = cache_specs(c, 32, 2176)
    per_token = sum(spec[k].shape[-1] for k in ("c_kv", "k_pe"))
    assert per_token == 576 and spec["c_kv"].shape == (27, 32, 2176, 512)


def test_the_benchmark_file_reads_as_the_preset_but_for_the_held_share():
    """``bench/configs/deepseek-v2-lite.json`` holds the published keys at
    its top level; read through the architecture module it is the preset
    with 8 of the 64 experts held."""
    from repro.configs.deepseek_v2_lite import CONFIG as c

    conf = model.load_config("deepseek-v2-lite", ROOT / "bench")
    assert "config" not in conf and conf["reduced"] == ["n_routed_experts"]
    assert conf["first_k_dense_replace"] == 1
    got = ARCH.arch_config(conf)
    assert got.moe == dataclasses.replace(c.moe, n_held=8)
    assert got.mla == c.mla and got.rope_scaling == c.rope_scaling
    assert (got.n_layers, got.d_model, got.n_heads, got.d_head,
            got.vocab_size, got.first_k_dense, got.d_ff_dense, got.d_ff) == (
        c.n_layers, c.d_model, c.n_heads, c.d_head, c.vocab_size,
        c.first_k_dense, c.d_ff_dense, c.d_ff)


@pytest.mark.parametrize("first", [0, 8])
def test_the_latent_expert_layer_adds_the_shared_experts_once(first):
    """The latent stack's feed-forward half of an expert layer: the held
    share's part for every pair routed to it, the shared expert once,
    and the counts of the pairs."""
    from repro.nn.transformer import _latent_ffn

    base = ARCH.arch_config(CONF)
    cfg = dataclasses.replace(base, dtype="float32", moe=dataclasses.replace(
        base.moe, n_experts=E, top_k=K, d_expert=F, n_shared=1,
        first_expert=first, n_held=4, norm_topk_prob=False))
    x, router, w_in, w_out = _experts(3)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    sh_in = jax.random.normal(ks[0], (D, 2 * F)) * D ** -0.5
    sh_out = jax.random.normal(ks[1], (F, D)) * F ** -0.5
    p = {"router": router, "moe_w_in": w_in[first:first + 4],
         "moe_w_out": w_out[first:first + 4], "sh_w_in": sh_in,
         "sh_w_out": sh_out}
    y, counts = _latent_ffn(p, x[None], cfg, None, 1, True)
    h = x @ sh_in
    want = (_plain_layer(x, router, w_in, w_out, range(first, first + 4),
                         False)
            + (jax.nn.silu(h[:, :F]) * h[:, F:]) @ sh_out)
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-5)
    assert int(counts.sum()) == x.shape[0] * K


@pytest.mark.parametrize("norm", [False, True])
def test_capacity_routing_honours_norm_topk_prob(norm):
    from repro.nn.moe import moe_ffn_local

    x, router, w_in, w_out = _experts(5)
    y, _ = moe_ffn_local(x, router, w_in, w_out, n_experts=E, top_k=K,
                         capacity=x.shape[0], e0=0, norm_topk_prob=norm)
    np.testing.assert_allclose(
        y, _plain_layer(x, router, w_in, w_out, range(E), norm),
        rtol=1e-5, atol=1e-5)
