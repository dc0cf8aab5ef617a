"""Plain float32 reference of the toy mixture-of-experts decoder.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, written
from the description of the program's MoE layer and imports nothing of
the program:

    x = embed[tokens]
    per layer:  h = rms(x) * w_ln1
                x += causal GQA attention of h (rotate_half rotary)
                h = rms(x) * w_ln2
                p = softmax(h W_router);  the top_k of p, renormalised
                x += sum_j p_j * E_j(h) + S(h)
                     E_e(h) = (silu(h W_in[e][:, :f]) * (h W_in[e][:, f:]))
                              W_out[e];  S the same of the shared weights
    logits = (rms(x) * w_final) embed^T

Every routed expert runs on every token and the router's weights pick
from them, so no token is dropped.  ``quant="fp8"`` is the control:
every projection's two operands rounded to float8 e4m3 (per-row
activation and per-column weight scales) before a float32 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant, spec="...d,df->...f"):
    """``a`` (..., d) by ``w`` whose input axis is the second from last."""
    if quant == "fp8":
        a, w = _q8(a, -1), _q8(w, -2)
    return jnp.einsum(spec, a, w)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _gated(h, w_in, w_out, quant, spec_in, spec_out):
    gu = _mm(h, w_in, quant, spec_in)
    gate, up = jnp.split(gu, 2, -1)
    return _mm(jax.nn.silu(gate) * up, w_out, quant, spec_out)


def block(x, w, m, quant=None):
    b, t, _ = x.shape
    eps, dh = m["eps"], m["dh"]
    pos = jnp.arange(t)
    h = _rms(x, 1.0 + w["ln1"], eps)
    q = _rotary(_mm(h, w["wq"], quant).reshape(b, t, m["H"], dh), pos,
                m["theta"])
    k = _rotary(_mm(h, w["wk"], quant).reshape(b, t, m["KV"], dh), pos,
                m["theta"])
    v = _mm(h, w["wv"], quant).reshape(b, t, m["KV"], dh)
    rep = m["H"] // m["KV"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + _mm(a.reshape(b, t, -1), w["wo"], quant)

    h = _rms(x, 1.0 + w["ln2"], eps)
    probs = jax.nn.softmax(_mm(h, w["router"], quant), -1)     # (b, t, E)
    top_p, top_i = jax.lax.top_k(probs, m["k"])
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    pick = jnp.sum(jax.nn.one_hot(top_i, m["E"]) * top_p[..., None], -2)
    experts = _gated(h, w["moe_w_in"], w["moe_w_out"], quant,
                     "btd,edf->btef", "btef,efd->bted")       # (b, t, E, d)
    routed = jnp.einsum("bte,bted->btd", pick, experts)
    shared = _gated(h, w["sh_w_in"], w["sh_w_out"], quant,
                    "...d,df->...f", "...d,df->...f")
    return x + routed + shared


def head(x, final_norm, embed, m, quant=None):
    h = _rms(x, 1.0 + final_norm, m["eps"])
    return _mm(h, embed.T, quant)


@functools.lru_cache(maxsize=None)
def _programs(mkey, quant):
    m = dict(mkey)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    embed = jax.jit(lambda e, tok: e.astype(jnp.float32)[tok])
    layer = jax.jit(lambda x, w: block(x, f32(w), m, quant))
    final = jax.jit(lambda x, idx, fn, e: head(
        jnp.take_along_axis(x, idx[..., None], 1), fn.astype(jnp.float32),
        e.astype(jnp.float32), m, quant))
    return embed, layer, final


def logits_at(m: dict, weights, tokens: np.ndarray, positions: np.ndarray,
              quant=None) -> np.ndarray:
    """float32 logits (N, K, V) at ``positions`` (N, K) of ``tokens``
    (N, T); ``weights`` is ``(global_fn, layer_fn)``."""
    embed_p, layer_p, final_p = _programs(tuple(sorted(m.items())), quant)
    glob_fn, layer_fn = weights
    with jax.default_matmul_precision("highest"):
        g = glob_fn()
        x = embed_p(g["embed"], jnp.asarray(tokens))
        for layer in range(m["L"]):
            x = layer_p(x, layer_fn(layer))
        lg = final_p(x, jnp.asarray(positions), g["final_norm"], g["embed"])
    return np.asarray(lg)
