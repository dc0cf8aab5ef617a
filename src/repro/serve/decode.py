"""Prefill and single-token decode per architecture family.

``prefill(params, cfg, batch)`` -> (last-token logits, decode state)
``decode_step(params, cfg, cache, tokens, pos)`` -> (logits, new state)

decode_step is the function lowered for the ``decode_*`` / ``long_*``
dry-run cells (one new token against a seq_len-deep cache).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import sites
from repro.configs.base import ArchConfig
from repro.nn.layers import rms_norm
from repro.nn.mlp import mlp_block, project_logits, run_layers, site_act
from repro.nn.moe import moe_block
from repro.nn.transformer import (
    _attn_apply,
    _decode_attn,
    _decoder_embed,
    _latent_ffn,
    _mla_decode,
    decoder_forward,
    encoder_forward,
    encdec_forward,
    hybrid_forward,
    latent_forward,
    latent_layers,
    rwkv_forward,
)
from repro.nn.attention import mha
from repro.nn.sharding import shard


# =========================================================================
# decoder-only (dense / moe / vlm)
# =========================================================================
def decoder_prefill(params, cfg, batch, max_seq: int | None = None,
                    lut_tables=None):
    tokens = batch["tokens"]
    x, _, kvs = decoder_forward(
        params, cfg, tokens, patches=batch.get("patches"), collect_kv=True,
        lut_tables=lut_tables)
    logits = project_logits(x[:, -1:], params["lm_head"], cfg, lut_tables)
    k, v = kvs
    cache = {"k": k, "v": v}
    if max_seq and max_seq > k.shape[2]:
        pad = max_seq - k.shape[2]
        cache = {
            n: jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            for n, c in cache.items()
        }
    return logits, cache


def decoder_decode_step(params, cfg, cache, tokens, pos,
                        lut_tables=None):
    x = _decoder_embed(params, cfg, tokens)
    int8 = "k_scale" in cache

    def body(x, inp, layer):
        rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
        if int8:
            p, kc, vc, ksc, vsc = inp
            h, (kc, ksc), (vc, vsc) = _decode_attn(
                p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg, kc, vc,
                pos, scales=(ksc, vsc), lut_tables=lut_tables, layer=layer)
        else:
            p, kc, vc = inp
            h, kc, vc = _decode_attn(
                p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg, kc, vc,
                pos, lut_tables=lut_tables, layer=layer)
        x = x + h
        hin = rms_norm(x, p["ln2"], cfg.norm_eps, rs)
        if cfg.moe:
            shared = None
            if cfg.moe.n_shared:
                shared = lambda z: mlp_block(
                    {"w_in": p["sh_w_in"], "w_out": p["sh_w_out"]}, z, cfg,
                    lut_tables, layer=layer)
            h, _ = moe_block(
                {"router": p["router"], "w_in": p["moe_w_in"],
                 "w_out": p["moe_w_out"]}, hin, cfg, shared_mlp=shared,
                lut_tables=lut_tables, layer=layer)
        else:
            h = mlp_block(p, hin, cfg, lut_tables, layer=layer)
        out = (kc, vc, ksc, vsc) if int8 else (kc, vc)
        return x + h, out

    if int8:
        xs = (params["blocks"], cache["k"], cache["v"], cache["k_scale"],
              cache["v_scale"])
        x, (ks, vs, kss, vss) = run_layers(body, x, xs,
                                           lut_tables=lut_tables)
        new_cache = {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss}
    else:
        x, (ks, vs) = run_layers(
            body, x, (params["blocks"], cache["k"], cache["v"]),
            lut_tables=lut_tables)
        new_cache = {"k": ks, "v": vs}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return logits, new_cache


# =========================================================================
# latent attention (DeepSeek-V2)
# =========================================================================
def latent_prefill(params, cfg, batch, max_seq: int | None = None,
                   lut_tables=None):
    """Expanded-attention prefill that writes the latent cache directly
    at ``max_seq`` positions (no padding copy)."""
    tokens = batch["tokens"]
    x, cache = latent_forward(params, cfg, tokens, lut_tables=lut_tables,
                              max_seq=max_seq or tokens.shape[1])
    logits = project_logits(x[:, -1:], params["lm_head"], cfg, lut_tables)
    return logits, cache


def latent_decode_step(params, cfg, cache, tokens, pos, lut_tables=None):
    """One token through absorbed latent attention: every layer reads
    its rows of the latent cache, and the token's entries of all layers
    are written at ``pos`` after the last one; the expert layers add
    their assignments to ``cache["expert_counts"]``."""
    from repro.nn.layers import embed_lookup

    x = embed_lookup(params["embed"], tokens)

    def layer_fn(p, x, counts, li, lut_layer, expert_layer):
        rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, lut_layer)
        row = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                     keepdims=False)
        h, new = _mla_decode(p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg,
                             row(cache["c_kv"]), row(cache["k_pe"]), pos,
                             lut_tables=lut_tables, layer=lut_layer)
        x = x + h
        y, routed = _latent_ffn(p, rms_norm(x, p["ln2"], cfg.norm_eps, rs),
                                cfg, lut_tables, lut_layer, expert_layer)
        if routed is not None:
            counts = counts + routed
        return x + y, counts, new

    x, counts, (c_new, pe_new) = latent_layers(
        params, cfg, x, layer_fn, cache.get("expert_counts"),
        lut_tables=lut_tables)
    cache = dict(cache)
    if counts is not None:
        cache["expert_counts"] = counts
    for name, val in (("c_kv", c_new), ("k_pe", pe_new)):
        cache[name] = jax.lax.dynamic_update_slice(cache[name], val,
                                                   (0, 0, pos, 0))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return project_logits(x, params["lm_head"], cfg, lut_tables), cache


# =========================================================================
# encdec (whisper)
# =========================================================================
def encdec_prefill(params, cfg, batch, max_seq: int | None = None,
                   lut_tables=None):
    # The encoder pass is one-shot per request and keeps the exact
    # activations; the decoder prefill and the decode loop serve the
    # per-layer LUT tables (stacked form scans, legacy form unrolls).
    enc = encoder_forward(params, cfg, batch["frames"])
    # per-layer cross K/V from the encoder output
    def xkv(p):
        b, s, d = enc.shape
        ek = jnp.einsum("bsd,dq->bsq", enc, p["xwk"]).reshape(
            b, s, cfg.n_kv_heads, cfg.d_head)
        ev = jnp.einsum("bsd,dq->bsq", enc, p["xwv"]).reshape(
            b, s, cfg.n_kv_heads, cfg.d_head)
        return ek, ev

    xks, xvs = jax.vmap(xkv)(params["dec_blocks"])
    x, kvs = encdec_forward(params, cfg, batch["tokens"], enc,
                            collect_kv=True, lut_tables=lut_tables)
    logits = project_logits(x[:, -1:], params["lm_head"], cfg, lut_tables)
    k, v = kvs
    cache = {"k": k, "v": v, "xk": xks.astype(k.dtype),
             "xv": xvs.astype(k.dtype)}
    return logits, cache


def encdec_decode_step(params, cfg, cache, tokens, pos, lut_tables=None):
    from repro.nn.layers import embed_lookup

    x = embed_lookup(params["embed"], tokens)

    def body(x, inp, layer):
        p, kc, vc, xk, xv = inp
        rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
        h, kc, vc = _decode_attn(
            p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg, kc, vc, pos,
            lut_tables=lut_tables, layer=layer)
        x = x + h
        xin = rms_norm(x, p["lnx"], cfg.norm_eps, rs)
        b = xin.shape[0]
        q = jnp.einsum("btd,dq->btq", xin, p["xwq"]).reshape(
            b, 1, cfg.n_heads, cfg.d_head)
        h = mha(q, xk, xv, causal=False,
                exp_fn=site_act(cfg, lut_tables, sites.ATTN_EXP, layer))
        h = jnp.einsum("btq,qd->btd", h.reshape(b, 1, cfg.q_dim), p["xwo"])
        x = x + h
        h = mlp_block(p, rms_norm(x, p["ln2"], cfg.norm_eps, rs), cfg,
                      lut_tables, layer=layer)
        return x + h, (kc, vc)

    x, (ks, vs) = run_layers(
        body, x,
        (params["dec_blocks"], cache["k"], cache["v"], cache["xk"],
         cache["xv"]),
        lut_tables=lut_tables)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return logits, {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"]}


# =========================================================================
# ssm (rwkv6) / hybrid (recurrentgemma)
# =========================================================================
def rwkv_prefill(params, cfg, batch, max_seq: int | None = None,
                 lut_tables=None):
    x, states = rwkv_forward(params, cfg, batch["tokens"],
                             collect_states=True, lut_tables=lut_tables)
    logits = project_logits(x[:, -1:], params["lm_head"], cfg, lut_tables)
    return logits, states


def rwkv_decode_step(params, cfg, cache, tokens, pos, lut_tables=None):
    x, states = rwkv_forward(params, cfg, tokens, states=cache,
                             lut_tables=lut_tables)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return logits, states


def hybrid_prefill(params, cfg, batch, max_seq: int | None = None,
                   lut_tables=None):
    x, states = hybrid_forward(params, cfg, batch["tokens"], mode="prefill",
                               lut_tables=lut_tables)
    logits = project_logits(x[:, -1:], params["lm_head"], cfg, lut_tables)
    return logits, states


def hybrid_decode_step(params, cfg, cache, tokens, pos, lut_tables=None):
    x, states = hybrid_forward(params, cfg, tokens, states=cache, pos=pos,
                               mode="decode", lut_tables=lut_tables)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return logits, states


PREFILL_FNS = {
    "dense": decoder_prefill, "moe": decoder_prefill, "vlm": decoder_prefill,
    "encdec": encdec_prefill, "ssm": rwkv_prefill, "hybrid": hybrid_prefill,
}
DECODE_FNS = {
    "dense": decoder_decode_step, "moe": decoder_decode_step,
    "vlm": decoder_decode_step, "encdec": encdec_decode_step,
    "ssm": rwkv_decode_step, "hybrid": hybrid_decode_step,
}


def prefill(params, cfg: ArchConfig, batch, max_seq=None, lut_tables=None):
    fn = latent_prefill if cfg.mla is not None else PREFILL_FNS[cfg.family]
    return fn(params, cfg, batch, max_seq, lut_tables=lut_tables)


def decode_step(params, cfg: ArchConfig, cache, tokens, pos,
                lut_tables=None):
    fn = (latent_decode_step if cfg.mla is not None
          else DECODE_FNS[cfg.family])
    return fn(params, cfg, cache, tokens, pos, lut_tables=lut_tables)


def prefill_replay(params, cfg: ArchConfig, cache, tokens, start_pos=0,
                   lut_tables=None):
    """Replay a (B, T) prompt through the single-token decode step with a
    ``lax.scan`` over positions: (last-token logits, filled cache).

    This is the batcher-level prefill for caches the full-sequence prefill
    cannot produce — the decode *write path* quantizes, so replaying into
    an int8 KV cache yields exactly the entries steady-state decode would
    have written (scales included), and the same LUT-compressed
    activations (``lut_tables``) run during ingestion as during decode.
    One compiled scan replaces T python-level step calls.
    """
    t = tokens.shape[1]

    def body(c, inp):
        tok, pos = inp
        logits, c = decode_step(params, cfg, c, tok, pos,
                                lut_tables=lut_tables)
        return c, logits

    xs = (jnp.swapaxes(tokens, 0, 1)[:, :, None],
          start_pos + jnp.arange(t))
    cache, logits = jax.lax.scan(body, cache, xs)
    return logits[-1], cache
