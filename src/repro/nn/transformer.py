"""Model assembly for every assigned architecture family.

One parameter-definition tree (`ParamDef`) is the single source of truth:
`init_params` materializes it, `param_specs` resolves the logical axes to
PartitionSpecs for a mesh, and `jax.eval_shape` over init gives dry-run
shapes.  All layer stacks run under `jax.lax.scan` over stacked (L, ...)
parameters so the lowered HLO stays compact for 512-device compiles.

Families:
  dense | moe | vlm  -> decoder-only transformer (GQA + RoPE [+ MoE/patches])
  ssm                -> RWKV6 (chunked GLA)
  hybrid             -> Griffin/RecurrentGemma (RG-LRU + local attention)
  encdec             -> Whisper (bidirectional encoder + causal decoder)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

from repro import sites

from .attention import decode_attend, mha, ring_decode_attend
from .layers import (
    embed_lookup,
    rms_norm,
    softmax_cross_entropy,
    truncated_normal_init,
)
from .mlp import mlp_block, project_logits, run_layers, site_act
from .moe import held_moe_block, moe_block
from .rglru import recurrent_block, recurrent_block_step
from .rope import apply_rope, yarn_attention_scale
from .sharding import current_mesh, layer_scan, named_sharding, shard
from .ssm import rwkv_channel_mix, rwkv_time_mix


# =========================================================================
# Parameter definitions
# =========================================================================
@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    scale: float = 1.0
    dtype: str | None = None   # None => cfg dtype


def _attn_defs(cfg: ArchConfig, L: int, d: int) -> dict[str, ParamDef]:
    defs = {
        "wq": ParamDef((L, d, cfg.q_dim), (None, "fsdp", "tp")),
        "wk": ParamDef((L, d, cfg.kv_dim), (None, "fsdp", "tp")),
        "wv": ParamDef((L, d, cfg.kv_dim), (None, "fsdp", "tp")),
        "wo": ParamDef((L, cfg.q_dim, d), (None, "tp", "fsdp")),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((L, cfg.d_head), (None, None), 0.0)
        defs["k_norm"] = ParamDef((L, cfg.d_head), (None, None), 0.0)
    return defs


def _mla_defs(cfg: ArchConfig, L: int, d: int) -> dict[str, ParamDef]:
    """Latent attention: queries of ``nope + rope`` dims a head, one
    ``kv_lora_rank`` latent and one rotary key a token (``wkv_a``), the
    latent's RMSNorm, and its up-projection to each head's key and value
    (``wkv_b``, ``[k_nope ; v]`` a head)."""
    a, h = cfg.mla, cfg.n_heads
    return {
        "wq": ParamDef((L, d, h * a.qk_head_dim), (None, "fsdp", "tp")),
        "wkv_a": ParamDef((L, d, a.kv_lora_rank + a.qk_rope_head_dim),
                          (None, "fsdp", None)),
        "kv_norm": ParamDef((L, a.kv_lora_rank), (None, None), 0.0),
        "wkv_b": ParamDef(
            (L, a.kv_lora_rank, h * (a.qk_nope_head_dim + a.v_head_dim)),
            (None, None, "tp")),
        "wo": ParamDef((L, h * a.v_head_dim, d), (None, "tp", "fsdp")),
    }


def _latent_defs(cfg: ArchConfig, defs: dict) -> dict:
    """Latent-attention decoder: ``first_k_dense`` leading layers with a
    dense MLP of ``d_ff_dense`` (``"lead"``), then the scanned stack
    (``"blocks"``) of expert layers that hold ``moe.held`` experts beside
    the shared ones."""
    k, d = cfg.first_k_dense, cfg.d_model
    n = cfg.n_layers - k

    def norms(L):
        return {"ln1": ParamDef((L, d), (None, None), 0.0),
                "ln2": ParamDef((L, d), (None, None), 0.0)}

    if k:
        defs["lead"] = (_mla_defs(cfg, k, d) | norms(k)
                        | _mlp_defs(cfg, k, d, cfg.d_ff_dense or cfg.d_ff))
    blocks = _mla_defs(cfg, n, d) | norms(n)
    m = cfg.moe
    if m is None:
        blocks |= _mlp_defs(cfg, n, d, cfg.d_ff)
    else:
        blocks["router"] = ParamDef((n, d, m.n_experts), (None, None, None))
        blocks["moe_w_in"] = ParamDef(
            (n, m.held, d, 2 * m.d_expert), (None, None, "fsdp", None))
        blocks["moe_w_out"] = ParamDef(
            (n, m.held, m.d_expert, d), (None, None, None, "fsdp"))
        if m.n_shared:
            blocks["sh_w_in"] = ParamDef(
                (n, d, 2 * m.d_expert * m.n_shared), (None, "fsdp", "tp"))
            blocks["sh_w_out"] = ParamDef(
                (n, m.d_expert * m.n_shared, d), (None, "tp", "fsdp"))
    defs["blocks"] = blocks
    return defs


def _mlp_defs(cfg: ArchConfig, L: int, d: int, ff: int) -> dict[str, ParamDef]:
    from .layers import is_gated
    ff_in = 2 * ff if is_gated(cfg.activation) else ff
    return {
        "w_in": ParamDef((L, d, ff_in), (None, "fsdp", "tp")),
        "w_out": ParamDef((L, ff, d), (None, "tp", "fsdp")),
    }


def _rwkv_defs(cfg: ArchConfig) -> dict[str, ParamDef]:
    L, d, ff = cfg.n_layers, cfg.d_model, cfg.d_ff
    vec = lambda scale=1.0: ParamDef((L, d), (None, None), scale)
    mat = lambda m, n, ax=(None, "fsdp", "tp"): ParamDef((L, m, n), ax)
    defs = {
        "ln1": vec(0.0), "ln2": vec(0.0), "ln_x": vec(0.0),
        "lora_a": ParamDef((L, d, 32), (None, None, None)),
        "decay_a": ParamDef((L, d, 64), (None, None, None)),
        "decay_b": ParamDef((L, 64, d), (None, None, None), 0.1),
        "decay_base": vec(0.5),
        "bonus": vec(0.5),
        "mu_ffn_k": vec(0.5), "mu_ffn_r": vec(0.5),
        "w_r": mat(d, d), "w_k": mat(d, d), "w_v": mat(d, d),
        "w_g": mat(d, d), "w_o": ParamDef((L, d, d), (None, "tp", "fsdp")),
        "w_ffn_k": ParamDef((L, d, ff), (None, "fsdp", "tp")),
        "w_ffn_v": ParamDef((L, ff, d), (None, "tp", "fsdp")),
        "w_ffn_r": mat(d, d),
    }
    for nm in ("r", "k", "v", "w", "g"):
        defs[f"mu_{nm}"] = vec(0.5)
        defs[f"lora_b_{nm}"] = ParamDef((L, 32, d), (None, None, None), 0.1)
    return defs


def _rec_defs(cfg: ArchConfig, L: int) -> dict[str, ParamDef]:
    d, drnn = cfg.d_model, cfg.d_rnn or cfg.d_model
    return {
        "w_in": ParamDef((L, d, drnn), (None, "fsdp", "tp")),
        "w_gate": ParamDef((L, d, drnn), (None, "fsdp", "tp")),
        "w_out": ParamDef((L, drnn, d), (None, "tp", "fsdp")),
        "conv_w": ParamDef((L, cfg.conv_width, drnn), (None, None, "tp")),
        "w_a": ParamDef((L, drnn, drnn), (None, "fsdp", "tp")),
        "w_x": ParamDef((L, drnn, drnn), (None, "fsdp", "tp")),
        "lam": ParamDef((L, drnn), (None, "tp"), 0.5),
    }


def param_defs(cfg: ArchConfig) -> dict[str, Any]:
    L, d = cfg.n_layers, cfg.d_model
    defs: dict[str, Any] = {
        "embed": ParamDef((cfg.vocab_size, d), ("tp", "fsdp")),
        "final_norm": ParamDef((d,), (None,), 0.0),
        "lm_head": ParamDef((d, cfg.vocab_size), ("fsdp", "tp")),
    }
    if cfg.family == "ssm":
        defs["blocks"] = _rwkv_defs(cfg)
        return defs
    if cfg.family == "hybrid":
        pattern = cfg.block_pattern or ("rec", "rec", "attn")
        n_groups = L // len(pattern)
        n_tail = L - n_groups * len(pattern)
        group: dict[str, Any] = {}
        for i, kind in enumerate(pattern):
            sub = (_rec_defs(cfg, n_groups) if kind == "rec"
                   else _attn_defs(cfg, n_groups, d))
            group[f"t{i}_{kind}"] = sub
            group[f"t{i}_ln"] = ParamDef((n_groups, d), (None, None), 0.0)
            group[f"m{i}"] = _mlp_defs(cfg, n_groups, d, cfg.d_ff)
            group[f"m{i}_ln"] = ParamDef((n_groups, d), (None, None), 0.0)
        defs["groups"] = group
        if n_tail:
            tail: dict[str, Any] = {}
            for i in range(n_tail):
                tail[f"t{i}_rec"] = _rec_defs(cfg, 1)
                tail[f"t{i}_ln"] = ParamDef((1, d), (None, None), 0.0)
                tail[f"m{i}"] = _mlp_defs(cfg, 1, d, cfg.d_ff)
                tail[f"m{i}_ln"] = ParamDef((1, d), (None, None), 0.0)
            defs["tail"] = tail
        return defs
    if cfg.family == "encdec":
        Le = cfg.n_encoder_layers
        enc = _attn_defs(cfg, Le, d) | _mlp_defs(cfg, Le, d, cfg.d_ff)
        enc["ln1"] = ParamDef((Le, d), (None, None), 0.0)
        enc["ln2"] = ParamDef((Le, d), (None, None), 0.0)
        dec = _attn_defs(cfg, L, d) | _mlp_defs(cfg, L, d, cfg.d_ff)
        for k_, v_ in list(_attn_defs(cfg, L, d).items()):
            dec["x" + k_] = v_
        dec["ln1"] = ParamDef((L, d), (None, None), 0.0)
        dec["lnx"] = ParamDef((L, d), (None, None), 0.0)
        dec["ln2"] = ParamDef((L, d), (None, None), 0.0)
        defs["enc_blocks"] = enc
        defs["dec_blocks"] = dec
        defs["enc_norm"] = ParamDef((d,), (None,), 0.0)
        return defs

    # decoder-only: dense / moe / vlm
    if cfg.mla is not None:
        return _latent_defs(cfg, defs)
    blocks = _attn_defs(cfg, L, d)
    blocks["ln1"] = ParamDef((L, d), (None, None), 0.0)
    blocks["ln2"] = ParamDef((L, d), (None, None), 0.0)
    if cfg.moe:
        m = cfg.moe
        blocks["router"] = ParamDef((L, d, m.n_experts), (None, None, None))
        blocks["moe_w_in"] = ParamDef(
            (L, m.held, d, 2 * m.d_expert), (None, "tp", "fsdp", None))
        blocks["moe_w_out"] = ParamDef(
            (L, m.held, m.d_expert, d), (None, "tp", None, "fsdp"))
        if m.n_shared:
            blocks["sh_w_in"] = ParamDef(
                (L, d, 2 * m.d_expert * m.n_shared), (None, "fsdp", "tp"))
            blocks["sh_w_out"] = ParamDef(
                (L, m.d_expert * m.n_shared, d), (None, "tp", "fsdp"))
    else:
        blocks |= _mlp_defs(cfg, L, d, cfg.d_ff)
    defs["blocks"] = blocks
    if cfg.family == "vlm":
        defs["patch_proj"] = ParamDef((d, d), (None, None))
    return defs


def init_params(cfg: ArchConfig, key: jax.Array):
    defs = param_defs(cfg)
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    dtype = cfg.dtype

    def mk(d: ParamDef, k):
        dt = d.dtype if d.dtype else dtype
        if d.scale == 0.0:
            return jnp.zeros(d.shape, dt)
        if len(d.shape) == 1 or d.shape[-1] <= 64 and len(d.shape) == 2:
            return (jax.random.normal(k, d.shape) * 0.02 * d.scale).astype(dt)
        return truncated_normal_init(k, d.shape, d.scale, dt)

    return jax.tree.unflatten(treedef, [mk(d, k) for d, k in zip(leaves, keys)])


def param_specs(cfg: ArchConfig, mesh, fsdp: bool = True) -> Any:
    """NamedShardings for all params. ``fsdp=False`` (serving) drops the
    ZeRO-3 axis so weights are only tensor-parallel (no per-step
    all-gathers on the decode path)."""
    defs = param_defs(cfg)

    def resolve(d: ParamDef):
        axes = tuple(a if (fsdp or a != "fsdp") else None for a in d.axes)
        return named_sharding(mesh, *axes, shape=d.shape)

    return jax.tree.map(resolve, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def param_pspecs(cfg: ArchConfig, mesh, fsdp: bool = True) -> Any:
    ns = param_specs(cfg, mesh, fsdp=fsdp)
    return jax.tree.map(lambda s: s.spec, ns,
                        is_leaf=lambda s: isinstance(s, jax.sharding.NamedSharding))


# =========================================================================
# Attention sub-block (shared by decoder-only / encdec / hybrid-attn)
# =========================================================================
def _attn_apply(p, x, cfg, *, causal=True, window=None, pos_offset=0,
                kv_override=None, rope=True, chunk_q=512, lut_tables=None,
                layer=None):
    """Returns (out, (k, v)) for cache building.

    ``lut_tables``/``layer`` resolve the attention-hosted registry sites
    (rope sine, softmax exp) — both ``None``-gated, so with the sites
    inactive the exact trig/softmax paths run verbatim.  The qk-norm
    stays exact (its tiny per-head reduction is not a registered site).
    """
    b, t, d = x.shape
    q = jnp.einsum("btd,dq->btq", x, p["wq"]).reshape(
        b, t, cfg.n_heads, cfg.d_head)
    if kv_override is None:
        k = jnp.einsum("btd,dq->btq", x, p["wk"]).reshape(
            b, t, cfg.n_kv_heads, cfg.d_head)
        v = jnp.einsum("btd,dq->btq", x, p["wv"]).reshape(
            b, t, cfg.n_kv_heads, cfg.d_head)
    else:
        k, v = kv_override
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if kv_override is None:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        positions = jnp.arange(t) + pos_offset
        sin_fn = site_act(cfg, lut_tables, sites.ROPE, layer)
        q = apply_rope(q, positions, cfg.rope_theta, sin_fn=sin_fn)
        if kv_override is None:
            k = apply_rope(k, positions, cfg.rope_theta, sin_fn=sin_fn)
    q = shard(q, "dp", None, "tp", None)
    k = shard(k, "dp", None, None, None)
    v = shard(v, "dp", None, None, None)
    out = mha(q, k, v, causal=causal, window=window, q_offset=pos_offset,
              chunk_q=chunk_q,
              exp_fn=site_act(cfg, lut_tables, sites.ATTN_EXP, layer))
    # constrain BEFORE the output projection: under exact_tp this resolves
    # to replicated, so the wo contraction is never partitioned over heads
    # (a partitioned contraction psums partial products and breaks the
    # sharded-serving bit-identity contract)
    out = shard(out, "dp", None, "tp", None)
    out = jnp.einsum("btq,qd->btd", out.reshape(b, t, cfg.q_dim), p["wo"])
    return shard(out, "dp", "sp", None), (k, v)


def _quantize_kv(x: jax.Array):
    """(B, 1, KV, Dh) -> (int8 values, (B, 1, KV) f32 scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _decode_attn(p, x, cfg, k_cache, v_cache, pos, *, window=None,
                 ring_pos=None, rope=True, scales=None, lut_tables=None,
                 layer=None):
    """Single-token attention; returns (out, k, v).

    Against a full cache (``window`` None) the cache is only read and
    ``k``/``v`` are the token's entries (B, 1, KV, Dh) in the cache's
    dtype, which the caller writes at ``pos`` after every layer.
    ``scales``: (k_scale, v_scale) for int8 caches — quantize the token,
    dequantize at read; ``k``/``v`` are then (int8 entries, scales)
    pairs.  Against a ring (``window``) the token is written into the
    ring first and ``k``/``v`` are the updated rings.
    """
    b = x.shape[0]
    q = jnp.einsum("btd,dq->btq", x, p["wq"]).reshape(
        b, 1, cfg.n_heads, cfg.d_head)
    k = jnp.einsum("btd,dq->btq", x, p["wk"]).reshape(
        b, 1, cfg.n_kv_heads, cfg.d_head)
    v = jnp.einsum("btd,dq->btq", x, p["wv"]).reshape(
        b, 1, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        pos_arr = jnp.full((1,), 0) + pos
        sin_fn = site_act(cfg, lut_tables, sites.ROPE, layer)
        q = apply_rope(q, pos_arr, cfg.rope_theta, sin_fn=sin_fn)
        k = apply_rope(k, pos_arr, cfg.rope_theta, sin_fn=sin_fn)
    exp_fn = site_act(cfg, lut_tables, sites.ATTN_EXP, layer)
    if window is None and scales is not None:
        k_scale, v_scale = scales
        (kq, ks), (vq, vs) = _quantize_kv(k), _quantize_kv(v)
        ks, vs = ks.astype(k_scale.dtype), vs.astype(v_scale.dtype)
        deq = lambda c, sc: c.astype(q.dtype) * sc[..., None].astype(q.dtype)
        out = decode_attend(q, k_cache, v_cache, deq(kq, ks), deq(vq, vs),
                            pos, k_scale=k_scale, v_scale=v_scale,
                            exp_fn=exp_fn)
        k, v = (kq, ks), (vq, vs)
    elif window is None:
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        out = decode_attend(q, k_cache, v_cache, k, v, pos, exp_fn=exp_fn)
    else:
        w = k_cache.shape[1]
        slot = pos % w
        k = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
        slots = jnp.arange(w)
        stored = pos - ((pos - slots) % w)
        out = ring_decode_attend(q, k, v, stored, pos, window,
                                 exp_fn=exp_fn)
    out = shard(out, "dp", None, "tp", None)
    out = jnp.einsum("btq,qd->btd", out.reshape(b, 1, cfg.q_dim), p["wo"])
    return out, k, v


# =========================================================================
# Decoder-only forward (dense / moe / vlm)
# =========================================================================
def _decoder_embed(params, cfg, tokens, patches=None):
    x = embed_lookup(params["embed"], tokens)
    if cfg.family == "vlm" and patches is not None:
        pre = jnp.einsum("bpd,de->bpe", patches.astype(x.dtype),
                         params["patch_proj"])
        x = jnp.concatenate([pre, x], axis=1)
    return x


def _decoder_block(p, x, cfg, lut_tables, pos_offset=0, collect_kv=False,
                   chunk_q=512, layer=None):
    rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
    h, kv = _attn_apply(p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg,
                        pos_offset=pos_offset, chunk_q=chunk_q,
                        lut_tables=lut_tables, layer=layer)
    x = x + h
    hin = rms_norm(x, p["ln2"], cfg.norm_eps, rs)
    if cfg.moe:
        shared = None
        if cfg.moe.n_shared:
            shared = lambda z: mlp_block(
                {"w_in": p["sh_w_in"], "w_out": p["sh_w_out"]}, z, cfg,
                lut_tables, layer=layer)
        h, aux = moe_block(
            {"router": p["router"], "w_in": p["moe_w_in"],
             "w_out": p["moe_w_out"]}, hin, cfg, shared_mlp=shared,
            lut_tables=lut_tables, layer=layer)
    else:
        h = mlp_block(p, hin, cfg, lut_tables, layer=layer)
        aux = jnp.zeros((), jnp.float32)
    x = x + h
    return x, aux, kv


def decoder_forward(params, cfg: ArchConfig, tokens, patches=None,
                    lut_tables=None, collect_kv=False, remat=False,
                    chunk_q=512):
    """Returns (hidden (B,T,d), aux, kv_stack | None).  A latent-attention
    config runs :func:`latent_forward` (no kv stack: its prefill builds
    the latent cache itself)."""
    if cfg.mla is not None:
        x, _ = latent_forward(params, cfg, tokens, lut_tables=lut_tables,
                              remat=remat)
        return x, jnp.zeros((), jnp.float32), None
    x = _decoder_embed(params, cfg, tokens, patches)

    def body(carry, p, layer):
        x = carry
        y, aux, kv = _decoder_block(p, x, cfg, lut_tables, chunk_q=chunk_q,
                                    layer=layer)
        out = (aux, kv) if collect_kv else (aux, None)
        return y, out

    x, (auxes, kvs) = run_layers(body, x, params["blocks"],
                                 lut_tables=lut_tables, remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.sum(auxes), kvs


# =========================================================================
# Latent-attention decoder (DeepSeek-V2): MLA, YaRN, leading dense layers
# =========================================================================
MLA_NORM_EPS = 1e-6     # the latent's RMSNorm (kv_a_layernorm)


def _mla_project(p, x, cfg, positions, lut_tables=None, layer=None):
    """Queries ``(q_nope (B,T,H,nope), q_pe (B,T,H,rope))`` and what the
    cache holds of each token: ``c_kv`` (B,T,R), the normed latent, and
    ``k_pe`` (B,T,rope), its rotated key, shared by all heads."""
    a = cfg.mla
    b, t, _ = x.shape
    q = jnp.einsum("btd,dq->btq", x, p["wq"]).reshape(
        b, t, cfg.n_heads, a.qk_head_dim)
    kv = jnp.einsum("btd,dr->btr", x, p["wkv_a"])
    rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
    c_kv = rms_norm(kv[..., :a.kv_lora_rank], p["kv_norm"], MLA_NORM_EPS, rs)
    sin_fn = site_act(cfg, lut_tables, sites.ROPE, layer)
    rope = functools.partial(apply_rope, positions=positions,
                             theta=cfg.rope_theta, sin_fn=sin_fn,
                             yarn=cfg.rope_scaling)
    q_pe = rope(q[..., a.qk_nope_head_dim:])
    k_pe = rope(kv[..., None, a.kv_lora_rank:])[:, :, 0]
    return q[..., :a.qk_nope_head_dim], q_pe, c_kv, k_pe


def _mla_scale(cfg) -> float:
    return yarn_attention_scale(cfg.mla.qk_head_dim, cfg.rope_scaling)


def _mla_chunk(b: int, h: int, t: int) -> int:
    """Query chunk of the expanded attention: the largest power of two
    up to 512 whose f32 score block (B, H, chunk, T) stays within 256 MiB."""
    chunk = 512
    while chunk > 16 and b * h * chunk * t * 4 > 2 ** 28:
        chunk //= 2
    return chunk


def _mla_attn(p, x, cfg, *, lut_tables=None, layer=None):
    """Expanded latent attention over a whole sequence (prefill): each
    head's key ``[W_kb,i c_kv ; k_pe]`` and value ``W_vb,i c_kv``.
    Returns ``(out, (c_kv, k_pe))``."""
    a, h = cfg.mla, cfg.n_heads
    b, t, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _mla_project(p, x, cfg, jnp.arange(t),
                                            lut_tables, layer)
    kv = jnp.einsum("btr,rk->btk", c_kv, p["wkv_b"]).reshape(
        b, t, h, a.qk_nope_head_dim + a.v_head_dim)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :a.qk_nope_head_dim],
         jnp.broadcast_to(k_pe[:, :, None], (b, t, h, a.qk_rope_head_dim))],
        axis=-1)
    out = mha(q, k, kv[..., a.qk_nope_head_dim:], causal=True,
              chunk_q=_mla_chunk(b, h, t), scale=_mla_scale(cfg),
              exp_fn=site_act(cfg, lut_tables, sites.ATTN_EXP, layer))
    out = jnp.einsum("btq,qd->btd", out.reshape(b, t, h * a.v_head_dim),
                     p["wo"])
    return out, (c_kv, k_pe)


def _mla_decode(p, x, cfg, c_cache, pe_cache, pos, *, lut_tables=None,
                layer=None):
    """Absorbed latent attention of one new token: the key half of
    ``W_kv_b`` is folded into the query and its value half into the
    output, so attention reads only the layer's latent cache (B, Tmax, R)
    and rotary key cache (B, Tmax, rope), beside the token's own entries.
    Returns ``(out, (c_kv, k_pe))``, the token's entries (B, 1, .) in the
    cache's dtype, which the caller writes at ``pos``."""
    from .attention import latent_decode_attend

    a, h = cfg.mla, cfg.n_heads
    b = x.shape[0]
    q_nope, q_pe, c_kv, k_pe = _mla_project(
        p, x, cfg, jnp.full((1,), 0) + pos, lut_tables, layer)
    c_kv = c_kv.astype(c_cache.dtype)
    k_pe = k_pe.astype(pe_cache.dtype)
    w = p["wkv_b"].reshape(a.kv_lora_rank, h,
                           a.qk_nope_head_dim + a.v_head_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                       w[..., :a.qk_nope_head_dim],
                       preferred_element_type=jnp.float32)
    ctx = latent_decode_attend(
        q_lat, q_pe[:, 0], c_cache, pe_cache, c_kv[:, 0], k_pe[:, 0], pos,
        _mla_scale(cfg),
        exp_fn=site_act(cfg, lut_tables, sites.ATTN_EXP, layer))
    o = jnp.einsum("bhr,rhv->bhv", ctx.astype(x.dtype),
                   w[..., a.qk_nope_head_dim:])
    out = jnp.einsum("bq,qd->bd", o.reshape(b, h * a.v_head_dim), p["wo"])
    return out[:, None], (c_kv, k_pe)


FFN_ROWS = 8192     # tokens a leading dense MLP takes at once


def _in_row_chunks(fn, x, rows: int = FFN_ROWS):
    """``fn`` over the tokens of ``x`` (B, T, d), ``rows`` at a time: a
    long prefill through a wide dense MLP then holds its (rows, d_ff)
    intermediates and not those of every token."""
    b, t, d = x.shape
    s = b * t
    if s <= rows:
        return fn(x)
    n = -(-s // rows)
    flat = jnp.pad(x.reshape(s, d), ((0, n * rows - s), (0, 0)))
    out = jax.lax.map(lambda c: fn(c[None])[0], flat.reshape(n, rows, d))
    return out.reshape(n * rows, d)[:s].reshape(b, t, d)


def _latent_ffn(p, x, cfg, lut_tables, layer, expert_layer):
    """The feed-forward half of a latent-attention layer: a dense MLP, or
    (``expert_layer``) the held experts plus the shared ones.  Returns
    ``(out, counts)``, ``counts`` None for a dense layer."""
    if not expert_layer:
        return _in_row_chunks(
            lambda z: mlp_block(p, z, cfg, lut_tables, layer=layer), x), None
    y, counts = held_moe_block(
        {"router": p["router"], "w_in": p["moe_w_in"],
         "w_out": p["moe_w_out"]}, x, cfg, lut_tables=lut_tables,
        layer=layer)
    if cfg.moe.n_shared:
        y = y + mlp_block({"w_in": p["sh_w_in"], "w_out": p["sh_w_out"]},
                          x, cfg, lut_tables, layer=layer)
    return y, counts


def latent_layers(params, cfg, x, layer_fn, carry, *, lut_tables=None,
                  remat=False):
    """Run the leading dense layers, then the scanned stack, through
    ``layer_fn(p, x, carry, li, lut_layer, expert_layer) -> (x, carry,
    y)``.  ``li`` is the global layer index (an int32 array in the
    stack); ``lut_layer`` the LUT layer id (``None`` in a plain scan, as
    :func:`repro.nn.mlp.run_layers` gives).  Returns ``(x, carry, ys)``,
    ``ys`` each layer's ``y`` stacked in layer order (``None`` if the
    layers give none)."""
    k = cfg.first_k_dense
    lead = []
    for i in range(k):
        p = jax.tree.map(lambda a, i=i: a[i], params["lead"])
        x, carry, y = layer_fn(p, x, carry, i, i, False)
        lead.append(y)

    def body(c, inp, layer):
        p, li = inp
        lut_layer = None if layer is None else layer + k
        x, carry, y = layer_fn(p, c[0], c[1], li, lut_layer,
                               cfg.moe is not None)
        return (x, carry), y

    n = cfg.n_layers - k
    (x, carry), ys = run_layers(
        body, (x, carry),
        (params["blocks"], jnp.arange(k, k + n, dtype=jnp.int32)),
        lut_tables=lut_tables, remat=remat)
    if lead and ys is not None:
        ys = jax.tree.map(lambda s, *ls: jnp.concatenate([jnp.stack(ls), s]),
                          ys, *lead)
    return x, carry, ys


def latent_forward(params, cfg, tokens, *, lut_tables=None, max_seq=None,
                   remat=False):
    """Full-sequence forward of a latent-attention decoder.  Returns
    ``(normed hidden (B,T,d), state)``; with ``max_seq`` the state is the
    decode state (:func:`repro.serve.kvcache.cache_specs`): the latent
    cache of every layer filled at positions ``0..T-1``, and the expert
    layers' assignment counts; otherwise ``None``."""
    b, t = tokens.shape
    x = embed_lookup(params["embed"], tokens)
    state = None
    if max_seq is not None:
        from repro.serve.kvcache import init_cache

        state = init_cache(cfg, b, max_seq, kv_dtype=cfg.dtype)

    def layer_fn(p, x, st, li, lut_layer, expert_layer):
        rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, lut_layer)
        h, (c_kv, k_pe) = _mla_attn(
            p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg,
            lut_tables=lut_tables, layer=lut_layer)
        x = x + h
        y, counts = _latent_ffn(p, rms_norm(x, p["ln2"], cfg.norm_eps, rs),
                                cfg, lut_tables, lut_layer, expert_layer)
        if st is not None:
            st = dict(st)
            for name, val in (("c_kv", c_kv), ("k_pe", k_pe)):
                st[name] = jax.lax.dynamic_update_slice(
                    st[name], val[None].astype(st[name].dtype),
                    (li, 0, 0, 0))
            if counts is not None:
                st["expert_counts"] = st["expert_counts"] + counts
        return x + y, st, None

    x, state, _ = latent_layers(params, cfg, x, layer_fn, state,
                                lut_tables=lut_tables, remat=remat)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), state


def decoder_loss(params, cfg, batch, lut_tables=None, remat=False,
                 chunk_q=512):
    patches = batch.get("patches")
    x, aux, _ = decoder_forward(params, cfg, batch["tokens"],
                                patches=patches, lut_tables=lut_tables,
                                remat=remat, chunk_q=chunk_q)
    if patches is not None:
        x = x[:, patches.shape[1]:]
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    loss = softmax_cross_entropy(logits, batch["labels"])
    if cfg.moe:
        loss = loss + cfg.moe.router_aux_weight * aux / cfg.n_layers
    return loss


# =========================================================================
# RWKV6 forward
# =========================================================================
def rwkv_forward(params, cfg, tokens, states=None, remat=False,
                 collect_states=False, lut_tables=None):
    """states: None (training) or per-layer decode state pytree with leaves
    stacked over layers: {"att_x": (L,B,1,d), "ffn_x": (L,B,1,d),
    "wkv": (L,B,H,N,N)}.  ``collect_states=True`` (prefill) returns the
    segment-final states from a full-sequence pass."""
    x = embed_lookup(params["embed"], tokens)
    decode = states is not None

    def body(carry, inp, layer):
        x = carry
        rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
        if decode:
            p, st = inp
            h, (ax, wkv) = rwkv_time_mix(
                p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg,
                x_last=st["att_x"], wkv_state=st["wkv"])
            x = x + h
            h, fx = rwkv_channel_mix(
                p, rms_norm(x, p["ln2"], cfg.norm_eps, rs), cfg,
                x_last=st["ffn_x"], lut_tables=lut_tables, layer=layer)
            x = x + h
            return x, {"att_x": ax, "ffn_x": fx, "wkv": wkv}
        p = inp
        h, (ax, wkv) = rwkv_time_mix(
            p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg)
        x = x + h
        h, fx = rwkv_channel_mix(
            p, rms_norm(x, p["ln2"], cfg.norm_eps, rs), cfg,
            lut_tables=lut_tables, layer=layer)
        x = x + h
        ys = ({"att_x": ax, "ffn_x": fx, "wkv": wkv} if collect_states
              else jnp.zeros((), jnp.float32))
        return x, ys

    xs = (params["blocks"], states) if decode else params["blocks"]
    x, out_states = run_layers(body, x, xs, lut_tables=lut_tables,
                               remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, (out_states if (decode or collect_states) else None)


def rwkv_loss(params, cfg, batch, lut_tables=None, remat=False, **_):
    x, _ = rwkv_forward(params, cfg, batch["tokens"], remat=remat)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return softmax_cross_entropy(logits, batch["labels"])


# =========================================================================
# Hybrid (Griffin / RecurrentGemma) forward
# =========================================================================
def _ring_from_segment(k, v, window):
    """Build the decode ring buffer from a prefill segment (positions
    0..T-1): slot s holds the latest position p with p % W == s."""
    t = k.shape[1]
    slots = jnp.arange(window)
    p = (t - 1) - ((t - 1 - slots) % window)
    valid = p >= 0
    idx = jnp.clip(p, 0, t - 1)
    kr = jnp.where(valid[None, :, None, None], k[:, idx], 0)
    vr = jnp.where(valid[None, :, None, None], v[:, idx], 0)
    return kr, vr


def _hybrid_temporal(kind, p, x, cfg, pos_offset, state=None, mode="train"):
    if kind == "rec":
        if mode == "decode":
            return recurrent_block_step(p, x, cfg, state)
        out, st = recurrent_block(p, x, cfg, state)
        return out, st
    # local attention
    if mode == "decode":
        out, kc, vc = _decode_attn(p, x, cfg, state["k"], state["v"],
                                   pos_offset, window=cfg.local_window)
        return out, {"k": kc, "v": vc}
    out, (k, v) = _attn_apply(p, x, cfg, causal=True,
                              window=cfg.local_window,
                              pos_offset=pos_offset)
    if mode == "prefill":
        kr, vr = _ring_from_segment(k, v, cfg.local_window)
        return out, {"k": kr, "v": vr}
    return out, {"k": k[:, :1], "v": v[:, :1]}  # placeholder (train)


def hybrid_forward(params, cfg, tokens, states=None, pos=0, remat=False,
                   mode=None, lut_tables=None):
    """Full-sequence forward. ``states`` (decode): pytree per group/tail.
    mode: train | prefill | decode (inferred from ``states`` if None)."""
    pattern = cfg.block_pattern or ("rec", "rec", "attn")
    x = embed_lookup(params["embed"], tokens)
    mode = mode or ("decode" if states is not None else "train")
    decode = mode == "decode"
    collect = mode in ("prefill", "decode")

    def group_body(carry, inp, group):
        x = carry
        if decode:
            p, st = inp
        else:
            p, st = inp, {}
        new_st = {}
        for i, kind in enumerate(pattern):
            # Global mlp-site index: groups are laid out contiguously, one
            # mlp per pattern element — matches serve.plans' L{i} numbering.
            layer = None if group is None else group * len(pattern) + i
            rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
            xin = rms_norm(x, p[f"t{i}_ln"], cfg.norm_eps, rs)
            h, s = _hybrid_temporal(kind, p[f"t{i}_{kind}"], xin, cfg, pos,
                                    state=st.get(f"t{i}") if decode else None,
                                    mode=mode)
            new_st[f"t{i}"] = s
            x = x + h
            h = mlp_block(p[f"m{i}"], rms_norm(x, p[f"m{i}_ln"],
                                               cfg.norm_eps, rs), cfg,
                          lut_tables, layer=layer)
            x = x + h
        return x, new_st if collect else jnp.zeros((), jnp.float32)

    xs = ((params["groups"], states["groups"]) if decode
          else params["groups"])
    x, g_states = run_layers(group_body, x, xs, lut_tables=lut_tables,
                             remat=remat)

    tail_states = {}
    if "tail" in params:
        n_groups = jax.tree.leaves(params["groups"])[0].shape[0]
        tail_base = n_groups * len(pattern)
        tp_ = params["tail"]
        i = 0
        while f"t{i}_rec" in tp_:
            p_rec = jax.tree.map(lambda a: a[0], tp_[f"t{i}_rec"])
            ln = tp_[f"t{i}_ln"][0]
            rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, tail_base + i)
            xin = rms_norm(x, ln, cfg.norm_eps, rs)
            st = states["tail"].get(f"t{i}") if decode else None
            if decode:
                h, s = recurrent_block_step(p_rec, xin, cfg, st)
            else:
                h, s = recurrent_block(p_rec, xin, cfg, st)
            tail_states[f"t{i}"] = s
            x = x + h
            mp = jax.tree.map(lambda a: a[0], tp_[f"m{i}"])
            # Tail layers run python-level, so their (concrete) global
            # mlp-site index is always available — stacked and unrolled
            # per-layer tables both resolve it.
            h = mlp_block(mp, rms_norm(x, tp_[f"m{i}_ln"][0],
                                       cfg.norm_eps, rs), cfg, lut_tables,
                          layer=tail_base + i)
            x = x + h
            i += 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    out_states = ({"groups": g_states, "tail": tail_states}
                  if collect else None)
    return x, out_states


def hybrid_loss(params, cfg, batch, lut_tables=None, remat=False, **_):
    x, _ = hybrid_forward(params, cfg, batch["tokens"], remat=remat)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return softmax_cross_entropy(logits, batch["labels"])


# =========================================================================
# Whisper (enc-dec) forward
# =========================================================================
def _sinusoid(n: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(n)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def encoder_forward(params, cfg, frames, remat=False):
    """frames: (B, n_frames, d) stub embeddings (DESIGN.md: frontend stub)."""
    x = frames.astype(cfg.dtype)
    x = x + _sinusoid(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    x = shard(x, "dp", None, None)

    def body(x, p):
        h, _ = _attn_apply(p, rms_norm(x, p["ln1"], cfg.norm_eps), cfg,
                           causal=False, rope=False)
        x = x + h
        h = mlp_block(p, rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        return x + h, jnp.zeros((), jnp.float32)

    if remat:
        body = jax.checkpoint(body)
    x, _ = layer_scan(body, x, params["enc_blocks"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def encdec_forward(params, cfg, tokens, enc_out, collect_kv=False,
                   remat=False, lut_tables=None):
    x = embed_lookup(params["embed"], tokens)

    def body(x, p, layer):
        rs = site_act(cfg, lut_tables, sites.NORM_RSQRT, layer)
        h, kv = _attn_apply(p, rms_norm(x, p["ln1"], cfg.norm_eps, rs), cfg,
                            causal=True, rope=True, lut_tables=lut_tables,
                            layer=layer)
        x = x + h
        # cross attention (encoder K/V computed per layer)
        xin = rms_norm(x, p["lnx"], cfg.norm_eps, rs)
        b, t, d = xin.shape
        q = jnp.einsum("btd,dq->btq", xin, p["xwq"]).reshape(
            b, t, cfg.n_heads, cfg.d_head)
        ek = jnp.einsum("bsd,dq->bsq", enc_out, p["xwk"]).reshape(
            b, -1, cfg.n_kv_heads, cfg.d_head)
        ev = jnp.einsum("bsd,dq->bsq", enc_out, p["xwv"]).reshape(
            b, -1, cfg.n_kv_heads, cfg.d_head)
        h = mha(q, ek, ev, causal=False,
                exp_fn=site_act(cfg, lut_tables, sites.ATTN_EXP, layer))
        h = shard(h, "dp", None, "tp", None)
        h = jnp.einsum("btq,qd->btd", h.reshape(b, t, cfg.q_dim), p["xwo"])
        x = x + h
        h = mlp_block(p, rms_norm(x, p["ln2"], cfg.norm_eps, rs), cfg,
                      lut_tables, layer=layer)
        out = (jnp.zeros((), jnp.float32), kv if collect_kv else None)
        return x + h, out

    x, (_, kvs) = run_layers(body, x, params["dec_blocks"],
                             lut_tables=lut_tables, remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, kvs


def encdec_loss(params, cfg, batch, lut_tables=None, remat=False, **_):
    enc = encoder_forward(params, cfg, batch["frames"], remat=remat)
    x, _ = encdec_forward(params, cfg, batch["tokens"], enc, remat=remat)
    logits = project_logits(x, params["lm_head"], cfg, lut_tables)
    return softmax_cross_entropy(logits, batch["labels"])


LOSS_FNS = {
    "dense": decoder_loss,
    "moe": decoder_loss,
    "vlm": decoder_loss,
    "ssm": rwkv_loss,
    "hybrid": hybrid_loss,
    "encdec": encdec_loss,
}


def loss_fn(cfg: ArchConfig):
    return functools.partial(LOSS_FNS[cfg.family], cfg=cfg)
