"""Plain float32 reference of DeepSeek-V2 (-Lite).

Written from the DeepSeek-V2 paper (arXiv:2405.04434, sections 2.1 and
2.2) and ``transformers``' ``modeling_deepseek.py``
(``DeepseekV2Attention``, ``DeepseekV2YarnRotaryEmbedding``,
``MoEGate``, ``DeepseekV2MoE``), in straightforward ``jax.numpy`` at
``highest`` matmul precision, layer by layer, with no kernel, no cache,
no tables and no batching tricks:

    x = embed[tokens]
    per layer:  h = rms(x) * w_ln1
                q = h Wq                    (heads of nope + rope dims)
                [c ; k_r] = h Wkv_a;  c_kv = rms(c, eps 1e-6) * w_kv
                q_pe, k_pe = yarn(q[.., nope:]), yarn(k_r)  (k_pe shared
                                                             by all heads)
                [k_nope_i ; v_i] = c_kv Wkv_b,i
                s_i = (q_nope_i k_nope_i + q_pe_i k_pe) * qk_dim^-0.5 * m^2
                x += [softmax(s_i, causal) v_i]_i  Wo
                h = rms(x) * w_ln2
                dense layer:  x += (silu(h W_gate) * (h W_up)) W_down
                expert layer: p = softmax(h W_router) over all E experts;
                              g_e = p_e if e is among the top k, else 0
                              (renormalised over the top k only with
                              norm_topk_prob);
                              x += sum over the held experts e of
                                   g_e * expert_e(h)
                                   + shared(h)
    logits = (rms(x) * w_final) W_head                 (untied head)

YaRN (``DeepseekV2YarnRotaryEmbedding``): the rotary pairs below the
correction dim of ``beta_fast`` keep the plain frequency, those above
that of ``beta_slow`` are divided by ``factor``, a linear ramp between;
cos and sin are scaled by ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``; ``m = mscale(factor, mscale_all_dim)``.

Each held expert is computed on every token and weighted by its gate,
zero where the router did not pick it: the plainest form of the chip's
share, the same share the program holds.  What the experts held
elsewhere would add is left out, as the program leaves it out.

Departures, each an equivalence:

* ``transformers`` de-interleaves the rotary dims of ``q_pe`` and
  ``k_pe`` (``view(.., d/2, 2).transpose``) before ``rotate_half``; this
  reference rotates halves directly.  The two differ by one fixed
  permutation of the rotary columns of ``Wq`` and ``Wkv_a``, applied to
  query and key alike, so random weights drawn with either convention
  give the same model family.
* ``routed_scaling_factor`` is 1, so it is not applied.

It imports nothing of the program.  ``quant="fp8"`` is the control:
every projection's two operands (the router's and the experts' too)
rounded to float8 e4m3 (per-row activation and per-column weight
scales) before a float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0
KV_NORM_EPS = 1e-6


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _q8(a, -1), _q8(w, -2)
    return jnp.einsum("...d,df->...f", a, w)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m: dict) -> np.ndarray:
    """YaRN inverse frequencies of the ``rope`` rotary dims."""
    dim, base = m["rope"], m["theta"]

    def corr(rot):
        return (dim * math.log(m["orig_ctx"] / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(m["beta_fast"])), 0)
    high = min(math.ceil(corr(m["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / m["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def _rotary(x, pos, m):
    """rotate_half rotary with YaRN frequencies on (B, T, H, rope)."""
    inv = jnp.asarray(yarn_inv_freq(m), jnp.float32)
    scale = (yarn_mscale(m["factor"], m["mscale"])
             / yarn_mscale(m["factor"], m["mscale_all"]))
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (T, r/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None] * scale
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(h, w_in, w_out, quant):
    f = w_out.shape[0]
    gu = _mm(h, w_in, quant)
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_out, quant)


def block(x, w, m, quant=None):
    """One layer on (B, T, d) float32; a dense layer has no router."""
    b, t, _ = x.shape
    h_, nope, rope, dv, r = m["H"], m["nope"], m["rope"], m["dv"], m["R"]
    pos = jnp.arange(t)
    h = _rms(x, 1.0 + w["ln1"], m["eps"])
    q = _mm(h, w["wq"], quant).reshape(b, t, h_, nope + rope)
    kv = _mm(h, w["wkv_a"], quant)
    c_kv = _rms(kv[..., :r], 1.0 + w["kv_norm"], KV_NORM_EPS)
    q_pe = _rotary(q[..., nope:], pos, m)
    k_pe = _rotary(kv[..., None, r:], pos, m)
    up = _mm(c_kv, w["wkv_b"], quant).reshape(b, t, h_, nope + dv)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_pe, (b, t, h_, rope))], -1)
    qq = jnp.concatenate([q[..., :nope], q_pe], -1)
    scale = ((nope + rope) ** -0.5
             * yarn_mscale(m["factor"], m["mscale_all"]) ** 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qq, k) * scale
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), up[..., nope:])
    x = x + _mm(a.reshape(b, t, h_ * dv), w["wo"], quant)
    h = _rms(x, 1.0 + w["ln2"], m["eps"])
    if "router" not in w:
        return x + _swiglu(h, w["w_in"], w["w_out"], quant)
    probs = jax.nn.softmax(_mm(h, w["router"], quant), -1)       # (B,T,E)
    top_p, top_i = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_i, m["E"]) * top_p[..., None], -2)
    y = _swiglu(h, w["sh_w_in"], w["sh_w_out"], quant)
    for e in range(m["held"]):
        y = y + gate[..., m["first"] + e, None] * _swiglu(
            h, w["moe_w_in"][e], w["moe_w_out"][e], quant)
    return x + y


def head(x, final_norm, lm_head, m, quant=None):
    """Logits (B, K, V) of hidden states (B, K, d)."""
    return _mm(_rms(x, 1.0 + final_norm, m["eps"]), lm_head, quant)


@functools.lru_cache(maxsize=None)
def _programs(mkey, quant):
    m = dict(mkey)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    embed = jax.jit(lambda e, tok: e.astype(jnp.float32)[tok])
    layer = jax.jit(lambda x, w: block(x, f32(w), m, quant))
    final = jax.jit(lambda x, idx, fn, hd: head(
        jnp.take_along_axis(x, idx[..., None], 1), fn.astype(jnp.float32),
        hd.astype(jnp.float32), m, quant))
    return embed, layer, final


def logits_at(m: dict, weights, tokens: np.ndarray, positions: np.ndarray,
              quant=None, rows: int | None = None) -> np.ndarray:
    """float32 logits (N, K, V) at ``positions`` (N, K) of ``tokens``
    (N, T), computed layer by layer and ``rows`` sequences at a time.

    ``weights`` is ``(global_fn, layer_fn)``: the first returns the
    embedding, final norm and head, the second layer ``i``'s weights."""
    n, t = tokens.shape
    if rows is None:    # keep one block's attention scores near 512 MiB
        rows = int(max(1, min(n, 2 ** 29 // (m["H"] * t * t * 4))))
    pad = (-n) % rows
    tok = np.concatenate([tokens, np.zeros((pad,) + tokens.shape[1:],
                                            tokens.dtype)])
    idx = np.concatenate([positions, np.zeros((pad,) + positions.shape[1:],
                                              positions.dtype)])
    embed_p, layer_p, final_p = _programs(tuple(sorted(m.items())), quant)
    glob_fn, layer_fn = weights
    out = []
    with jax.default_matmul_precision("highest"):
        g = glob_fn()
        xs = [embed_p(g["embed"], jnp.asarray(tok[i:i + rows]))
              for i in range(0, len(tok), rows)]
        for layer in range(m["L"]):
            w = layer_fn(layer)
            xs = [layer_p(x, w) for x in xs]
            del w
        for j, x in enumerate(xs):
            lg = final_p(x, jnp.asarray(idx[j * rows:(j + 1) * rows]),
                         g["final_norm"], g["lm_head"])
            out.append(np.asarray(lg))
    return np.concatenate(out)[:n]
