"""Attention: GQA with causal/local/cross variants, query-chunked.

Design notes (TPU roofline):
  * Scores are computed per query chunk (``lax.map`` over chunks) so the
    (Tq, Tk) matrix never materializes beyond ``(B, H, Cq, Tk)`` — the
    pure-JAX equivalent of flash attention's memory behavior, and it keeps
    the lowered HLO small for the 512-device dry-run compiles.
  * GQA never expands K/V to query heads: queries reshape to
    (B, T, KV, H/KV, Dh) and contract against (B, T, KV, Dh) directly.
  * Softmax in f32; all matmuls accumulate in f32.
  * Decode (Tq=1) reads a KV cache whose sequence dim is sharded over the
    model axis ("tp"); GSPMD inserts the partial-softmax reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .sharding import shard

NEG_INF = -1e30


def masked_softmax(s: jax.Array, exp_fn=None) -> jax.Array:
    """Softmax over the last axis of NEG_INF-masked f32 scores.

    With ``exp_fn=None`` this is ``jax.nn.softmax`` verbatim — the exact
    golden path, byte-identical to the pre-registry forward.  With an
    ``exp_fn`` (the attention-exp LUT site) the exponential runs through
    the callable on max-shifted scores; masked entries are re-zeroed
    *after* the lookup (a clipped-domain table maps NEG_INF to
    ``exp(x_lo)``, not 0) and the normalizer is guarded so fully-masked
    rows (padded/invalid positions) produce zeros instead of NaN.
    """
    if exp_fn is None:
        return jax.nn.softmax(s, axis=-1)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = exp_fn(s - m)
    e = jnp.where(s > NEG_INF * 0.5, e, 0.0)
    tot = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.where(tot > 0, e / tot, 0.0)


def _chunk_scores(qc, k, v, pos_q, pos_k, *, causal, window, scale,
                  exp_fn=None):
    """One query chunk against a key set.

    qc: (B, Cq, KV, G, Dh); k/v: (B, Tk, KV, Dh);
    pos_q: (Cq,), pos_k: (Tk,) absolute positions (pos < 0 => invalid key).
    """
    s = jnp.einsum(
        "bqkgd,btkd->bkgqt", qc, k, preferred_element_type=jnp.float32
    ) * scale
    mask = (pos_k[None, :] >= 0)
    if causal:
        mask = mask & (pos_q[:, None] >= pos_k[None, :])
    if window is not None:
        mask = mask & (pos_q[:, None] - pos_k[None, :] < window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = masked_softmax(s, exp_fn)
    out = jnp.einsum(
        "bkgqt,btkd->bqkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


def mha(
    q: jax.Array,          # (B, Tq, H, Dh)
    k: jax.Array,          # (B, Tk, KV, Dh)
    v: jax.Array,          # (B, Tk, KV, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    chunk_q: int = 512,
    exp_fn=None,
    scale: float | None = None,
) -> jax.Array:
    """General GQA attention. Returns (B, Tq, H, Dv), ``Dv`` the value
    head size (latent attention's values are narrower than its keys).
    ``scale`` defaults to ``Dh**-0.5``."""
    b, tq, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    dv = v.shape[-1]
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(b, tq, kv, g, dh)
    pos_k = jnp.arange(k.shape[1]) + k_offset

    if tq <= chunk_q:
        pos_q = jnp.arange(tq) + q_offset
        out = _chunk_scores(qg, k, v, pos_q, pos_k,
                            causal=causal, window=window, scale=scale,
                            exp_fn=exp_fn)
        return out.reshape(b, tq, h, dv)

    pad = (-tq) % chunk_q
    if pad:  # e.g. VLM prefix: 4096 tokens + 256 patches
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        tq_p = tq + pad
    else:
        tq_p = tq
    nc = tq_p // chunk_q
    qs = qg.reshape(b, nc, chunk_q, kv, g, dh).transpose(1, 0, 2, 3, 4, 5)

    def body(args):
        qc, c = args
        pos_q = jnp.arange(chunk_q) + q_offset + c * chunk_q
        return _chunk_scores(qc, k, v, pos_q, pos_k,
                             causal=causal, window=window, scale=scale,
                             exp_fn=exp_fn)

    outs = jax.lax.map(body, (qs, jnp.arange(nc)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, tq_p, kv, g, dv)
    return out[:, :tq].reshape(b, tq, h, dv)


def decode_attend(
    q: jax.Array,          # (B, 1, H, Dh)
    k_cache: jax.Array,    # (B, Tmax, KV, Dh) — seq dim tp-sharded
    v_cache: jax.Array,
    k_new: jax.Array,      # (B, 1, KV, Dh): the new token's key and value
    v_new: jax.Array,
    pos: jax.Array,        # scalar: the new token's position (0-based)
    k_scale: jax.Array | None = None,  # (B, Tmax, KV) int8-cache scales
    v_scale: jax.Array | None = None,
    exp_fn=None,
) -> jax.Array:
    """Single-token decode against the cached positions before ``pos``
    and the token's own key and value, taken beside the cache, not from
    it (as :func:`latent_decode_attend`): the caller writes the token's
    entries once, after every layer, and the step copies no cache.

    With FAST_STREAM the scores dot accumulates in the stream dtype (the
    contraction is only Dh=128 wide — safe) which avoids the CPU-XLA
    bf16->f32 materialization of the whole cache; the value contraction
    (Tmax wide) always accumulates in f32.  int8 caches (k_scale/v_scale
    given) dequantize at the consumer; ``k_new``/``v_new`` come in the
    dtype the cache reads as.
    """
    from .layers import FAST_STREAM

    b, tmax, kvh, dh = k_cache.shape
    h = q.shape[2]
    g = h // kvh
    qg = q.reshape(b, 1, kvh, g, dh)
    kc = k_cache
    vc = v_cache
    if k_scale is not None:
        kc = kc.astype(q.dtype) * k_scale[..., None].astype(q.dtype)
        vc = vc.astype(q.dtype) * v_scale[..., None].astype(q.dtype)

    def scores(k):
        if FAST_STREAM:
            return jnp.einsum("bqkgd,btkd->bkgqt", qg, k).astype(jnp.float32)
        return jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                          preferred_element_type=jnp.float32)

    s = jnp.concatenate([scores(kc), scores(k_new)], -1) * (dh ** -0.5)
    t = jnp.arange(tmax + 1)
    s = jnp.where(((t < pos) | (t == tmax))[None, None, None], s, NEG_INF)
    p = masked_softmax(s, exp_fn)
    dot = functools.partial(jnp.einsum, "bkgqt,btkd->bqkgd",
                            preferred_element_type=jnp.float32)
    out = (dot(p[..., :tmax].astype(vc.dtype), vc)
           + dot(p[..., tmax:].astype(v_new.dtype), v_new)).astype(q.dtype)
    return out.reshape(b, 1, h, dh)


def latent_decode_attend(
    q_lat: jax.Array,      # (B, H, R): queries folded into the latent space
    q_pe: jax.Array,       # (B, H, P): rotary part of the queries
    c_kv: jax.Array,       # (B, Tmax, R) latent cache
    k_pe: jax.Array,       # (B, Tmax, P) rotary key cache, shared by heads
    c_new: jax.Array,      # (B, R): the new token's latent
    pe_new: jax.Array,     # (B, P): the new token's rotary key
    pos: jax.Array,        # scalar: the new token's position (0-based)
    scale: float,
    exp_fn=None,
) -> jax.Array:
    """Single-token latent attention that reads the latent cache alone.
    Each head scores ``q_lat . c + q_pe . k_pe`` over the cached
    positions before ``pos`` and over the new token, and returns its
    softmax-weighted sum of latents (B, H, R); the caller applies each
    head's value up-projection (absorbed MLA, DeepSeek-V2 section
    2.1.2).  The new token is taken beside the cache, not from it, so a
    step reads the cache and writes it once, after every layer."""
    tmax = c_kv.shape[1]
    dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    s = (dot("bhr,btr->bht", q_lat.astype(c_kv.dtype), c_kv)
         + dot("bhp,btp->bht", q_pe.astype(k_pe.dtype), k_pe))
    s_new = (dot("bhr,br->bh", q_lat.astype(c_new.dtype), c_new)
             + dot("bhp,bp->bh", q_pe.astype(pe_new.dtype), pe_new))
    s = jnp.where(jnp.arange(tmax)[None, None] < pos, s, NEG_INF)
    p = masked_softmax(jnp.concatenate([s, s_new[..., None]], -1) * scale,
                       exp_fn)
    return (dot("bht,btr->bhr", p[..., :tmax].astype(c_kv.dtype), c_kv)
            + p[..., tmax:] * c_new[:, None].astype(jnp.float32))


def ring_decode_attend(
    q: jax.Array,          # (B, 1, H, Dh)
    k_ring: jax.Array,     # (B, W, KV, Dh) ring buffer
    v_ring: jax.Array,
    ring_pos: jax.Array,   # (W,) absolute position stored in each slot
    pos: jax.Array,
    window: int,
    exp_fn=None,
) -> jax.Array:
    """Decode against a sliding-window ring buffer (hybrid local layers)."""
    b, w, kvh, dh = k_ring.shape
    h = q.shape[2]
    g = h // kvh
    qg = q.reshape(b, 1, kvh, g, dh)
    s = jnp.einsum(
        "bqkgd,btkd->bkgqt", qg, k_ring,
        preferred_element_type=jnp.float32,
    ) * (dh ** -0.5)
    valid = (ring_pos <= pos) & (ring_pos > pos - window) & (ring_pos >= 0)
    s = jnp.where(valid[None, None, None, None], s, NEG_INF)
    p = masked_softmax(s, exp_fn)
    out = jnp.einsum(
        "bkgqt,btkd->bqkgd", p.astype(v_ring.dtype), v_ring,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return out.reshape(b, 1, h, dh)
