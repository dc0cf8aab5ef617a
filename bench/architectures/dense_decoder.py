"""A dense decoder with grouped-query attention and a gated silu MLP
(Qwen3, Phi-3/Phi-4-mini): its sizes, the program's ``ArchConfig``, its
weights from the seed, and the work a call has to do.

Weights are the benchmark's, not the program's: :func:`layer_weights`
draws one layer from ``fold_in(seed key, layer)`` in the published
layout (separate gate and up projections, RMSNorm weights), and
:func:`program_params` lays the same numbers out as the program's
parameter tree in one jitted call.  The plain reference draws the same
layers again, one at a time, so it takes nothing the program made.

Work is counted from the model's shapes, not the implementation, so a
later kernel or layout is measured against the same work:

* matmul FLOPs: 2 per multiply-add of every projection of a token
  (q, k, v, o; gate, up, down);
* attention FLOPs: ``4 * heads * head_dim`` per key a query attends
  (scores and the weighted sum), over the keys causally visible;
* the output head, ``2 * d_model * vocab``, only where logits are used;
* the LUT site (``mlp``): each MLP activation element read and written
  at the served dtype, plus one layer's served table bytes per layer
  call.

``m`` is :func:`dims` of the configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.model import NORM_SD, _normal, seed_keys


def dims(conf: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file."""
    c = conf["config"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    dh = c.get("head_dim") or d // h
    rot = int(round(dh * c.get("partial_rotary_factor", 1.0)))
    if c.get("hidden_act") != "silu":
        raise ValueError(f"{conf['name']}: only gated silu MLPs are "
                         f"benchmarked, got {c.get('hidden_act')!r}")
    if c.get("rope_scaling"):
        raise ValueError(f"{conf['name']}: rope_scaling is not run")
    return {"L": c["num_hidden_layers"], "d": d, "H": h,
            "KV": c["num_key_value_heads"], "dh": dh,
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
            "rot": rot, "qk_norm": bool(conf.get("qk_norm", False)),
            "tied": bool(c.get("tie_word_embeddings", False))}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig

    m = dims(conf)
    if m["rot"] != m["dh"]:
        raise ValueError(f"{conf['name']}: the program rotates the whole "
                         f"head; partial_rotary_factor must be 1.0")
    serving = conf.get("serving", {})
    return ArchConfig(
        name=conf["name"], family=conf.get("family", "dense"),
        n_layers=m["L"], d_model=m["d"], n_heads=m["H"],
        n_kv_heads=m["KV"], d_head=m["dh"], d_ff=m["ff"],
        vocab_size=m["V"], activation="swiglu", qk_norm=m["qk_norm"],
        rope_theta=m["theta"], norm_eps=m["eps"],
        dtype=conf["config"].get("torch_dtype", "bfloat16"),
        tie_embeddings=m["tied"],
        lut_act_bits_in=serving.get("lut_act_bits_in", 10),
        lut_act_bits_out=serving.get("lut_act_bits_out", 10))


def layer_weights(m: dict, key, layer, dtype=jnp.bfloat16) -> dict:
    """One decoder layer in the published layout; every layer has the
    same one, so ``layer`` is not read.  ``w_in`` is the gate and up
    projections side by side, ``[:, :ff]`` the gate."""
    d, q, kv, ff = m["d"], m["H"] * m["dh"], m["KV"] * m["dh"], m["ff"]
    ks = jax.random.split(key, 9)
    w = {"wq": _normal(ks[0], (d, q), d ** -0.5, dtype),
         "wk": _normal(ks[1], (d, kv), d ** -0.5, dtype),
         "wv": _normal(ks[2], (d, kv), d ** -0.5, dtype),
         "wo": _normal(ks[3], (q, d), q ** -0.5, dtype),
         "w_in": _normal(ks[4], (d, 2 * ff), d ** -0.5, dtype),
         "w_out": _normal(ks[5], (ff, d), ff ** -0.5, dtype),
         # RMSNorm weights are stored as offsets from 1 (the program's
         # parametrisation); the reference adds the 1 back
         "ln1": _normal(ks[6], (d,), NORM_SD, dtype),
         "ln2": _normal(ks[7], (d,), NORM_SD, dtype)}
    if m["qk_norm"]:
        kq, kk = jax.random.split(ks[8])
        w["q_norm"] = _normal(kq, (m["dh"],), NORM_SD, dtype)
        w["k_norm"] = _normal(kk, (m["dh"],), NORM_SD, dtype)
    return w


def global_weights(m: dict, key, dtype=jnp.bfloat16) -> dict:
    ke, kn = jax.random.split(key)
    return {"embed": _normal(ke, (m["V"], m["d"]), m["d"] ** -0.5, dtype),
            "final_norm": _normal(kn, (m["d"],), NORM_SD, dtype)}


def program_params(conf: dict, seed: int):
    """The program's parameter tree, on the device, from one jitted
    call.  The output head is the embedding's transpose (tied)."""
    m = dims(conf)
    glob, layers = seed_keys(seed, m["L"])

    def build(gk, lks):
        g = global_weights(m, gk)
        blocks = jax.lax.map(lambda k: layer_weights(m, k, None), lks)
        return {"embed": g["embed"], "final_norm": g["final_norm"],
                "lm_head": g["embed"].T, "blocks": blocks}

    return jax.jit(build)(glob, jnp.stack(layers))


# =========================================================================
# work counts
# =========================================================================
def token_matmul_flops(m: dict) -> float:
    """One token through every layer's projections."""
    d, q, kv, ff = m["d"], m["H"] * m["dh"], m["KV"] * m["dh"], m["ff"]
    return 2.0 * m["L"] * (d * (q + 2 * kv) + q * d + 3 * d * ff)


def attn_flops(m: dict, keys: float) -> float:
    """One query attending ``keys`` keys in every layer."""
    return 4.0 * m["L"] * m["H"] * m["dh"] * keys


def head_flops(m: dict) -> float:
    return 2.0 * m["d"] * m["V"]


def prompt_flops(m: dict, length: int, start: int = 0) -> float:
    """Ingesting ``length`` tokens at positions ``start..`` of one
    sequence, without the output head."""
    keys = sum(start + i + 1 for i in range(length))
    return length * token_matmul_flops(m) + attn_flops(m, keys)


def generate_flops(m: dict, batch: int, prompt: int, new_tokens: int
                   ) -> float:
    """One offline ``generate`` call: prefill with the head at the last
    position, then ``new_tokens`` decode steps each with the head."""
    per_row = prompt_flops(m, prompt) + head_flops(m)
    for i in range(new_tokens):
        per_row += prompt_flops(m, 1, prompt + i) + head_flops(m)
    return batch * per_row


def lut_bytes(m: dict, elements: float, table_bytes_per_layer: float,
              layer_calls: int, dtype_bytes: int = 2) -> float:
    """HBM bytes a LUT site must move: ``elements`` activations in and
    out, and one layer's tables per layer call."""
    return 2.0 * dtype_bytes * elements + table_bytes_per_layer * layer_calls


def generate_lut(m: dict, batch: int, prompt: int, new_tokens: int,
                 site_bytes: dict) -> float:
    """LUT bytes of one ``generate`` call (prefill + decode steps) at
    the ``mlp`` site, whose served tables take ``site_bytes["mlp"]``
    bytes a layer."""
    tokens = batch * (prompt + new_tokens)
    calls = m["L"] * (1 + new_tokens)
    return lut_bytes(m, tokens * m["ff"] * m["L"], site_bytes["mlp"],
                     calls)
