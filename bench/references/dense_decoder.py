"""Plain float32 reference of a dense decoder with grouped-query attention.

Written from the published description of Qwen3 and Phi-3/Phi-4-mini
(``transformers``' ``modeling_qwen3.py`` / ``modeling_phi3.py``), in
straightforward ``jax.numpy`` at ``highest`` matmul precision, with no
kernel, no cache and no batching tricks:

    x = embed[tokens]
    per layer:  h = rms(x) * w_ln1
                q, k, v = h Wq, h Wk, h Wv          (heads of head_dim)
                q, k = rms(q) * w_qn, rms(k) * w_kn  (qk_norm models only)
                q, k = rotary(q), rotary(k)          (rotate_half form,
                                                     first rot dims)
                x += softmax(q k^T / sqrt(head_dim), causal) v  Wo
                h = rms(x) * w_ln2
                x += (silu(h W_gate) * (h W_up)) W_down
    logits = (rms(x) * w_final) embed^T              (tied head)

It imports nothing of the program.  Weights come from the benchmark's
own generator, one layer at a time, so the reference fits beside
nothing else on the chip.  ``quant="fp8"`` is the control: every
projection's two operands rounded to float8 e4m3 (per-row activation and
per-column weight scales) before a float32 product.
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


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.einsum("...d,df->...f", a, w)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, pos, theta, rot):
    """rotate_half rotary on the first ``rot`` dims of (B, T, H, dh)."""
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (T, rot/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, -1)
    rolled = jnp.concatenate([-x2, x1], -1)
    return jnp.concatenate([xr * cos + rolled * sin, xp], -1)


def block(x, w, m, quant=None):
    """One decoder layer on (B, T, d) float32."""
    b, t, _ = x.shape
    eps, dh = m["eps"], m["dh"]
    pos = jnp.arange(t)
    h = _rms(x, 1.0 + w["ln1"], eps)
    q = _mm(h, w["wq"], quant).reshape(b, t, m["H"], dh)
    k = _mm(h, w["wk"], quant).reshape(b, t, m["KV"], dh)
    v = _mm(h, w["wv"], quant).reshape(b, t, m["KV"], dh)
    if m["qk_norm"]:
        q = _rms(q, 1.0 + w["q_norm"], eps)
        k = _rms(k, 1.0 + w["k_norm"], eps)
    q = _rotary(q, pos, m["theta"], m["rot"])
    k = _rotary(k, pos, m["theta"], m["rot"])
    rep = m["H"] // m["KV"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + _mm(a.reshape(b, t, -1), w["wo"], quant)
    h = _rms(x, 1.0 + w["ln2"], eps)
    gu = _mm(h, w["w_in"], quant)
    gate, up = gu[..., :m["ff"]], gu[..., m["ff"]:]
    return x + _mm(jax.nn.silu(gate) * up, w["w_out"], quant)


def head(x, final_norm, embed, m, quant=None):
    """Logits (B, K, V) of hidden states (B, K, d)."""
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
              quant=None, rows: int | None = None) -> np.ndarray:
    """float32 logits (N, K, V) at ``positions`` (N, K) of ``tokens``
    (N, T), computed layer by layer and ``rows`` sequences at a time.

    ``weights`` is ``(global_fn, layer_fn)``: the first returns the
    embedding and final norm, the second layer ``i``'s weights."""
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
                         g["final_norm"], g["embed"])
            out.append(np.asarray(lg))
    return np.concatenate(out)[:n]
