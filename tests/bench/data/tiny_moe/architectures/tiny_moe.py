"""A toy mixture-of-experts decoder: the program's ``moe`` family at
smoke size, added to a copy of the benchmark as new files only.

Grouped-query attention as in the dense decoder; in place of its MLP a
softmax router over ``n_routed_experts``, the ``num_experts_per_tok``
best renormalised, and ``n_shared_experts`` always-on experts merged
into one gated MLP.  The program serves the routed experts' activation
at the ``expert`` LUT site and the shared one's at the ``mlp`` site.

Weights are drawn in the program's layout (fused gate|up, the gate
first), one layer from ``fold_in(seed key, layer)``.  Work counts follow
the model: each token runs its ``top_k`` experts and the shared one,
whatever capacity the program's buffers pad to.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.model import NORM_SD, _normal, seed_keys


def dims(conf: dict) -> dict:
    c = conf["config"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": d, "H": h,
            "KV": c["num_key_value_heads"], "dh": c["head_dim"],
            "f": c["moe_intermediate_size"], "E": c["n_routed_experts"],
            "k": c["num_experts_per_tok"], "S": c["n_shared_experts"],
            "cf": float(c["capacity_factor"]), "V": c["vocab_size"],
            "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
            "qk_norm": bool(conf.get("qk_norm", False)),
            "tied": bool(c.get("tie_word_embeddings", False))}


def arch_config(conf: dict):
    from repro.configs.base import ArchConfig, MoEConfig

    m = dims(conf)
    serving = conf.get("serving", {})
    return ArchConfig(
        name=conf["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["KV"], d_head=m["dh"], d_ff=m["f"],
        vocab_size=m["V"], activation="swiglu", qk_norm=m["qk_norm"],
        rope_theta=m["theta"], norm_eps=m["eps"],
        dtype=conf["config"].get("torch_dtype", "bfloat16"),
        tie_embeddings=m["tied"],
        moe=MoEConfig(n_experts=m["E"], top_k=m["k"], d_expert=m["f"],
                      n_shared=m["S"], capacity_factor=m["cf"]),
        lut_act_bits_in=serving.get("lut_act_bits_in", 10),
        lut_act_bits_out=serving.get("lut_act_bits_out", 10))


def layer_weights(m: dict, key, layer, dtype=jnp.bfloat16) -> dict:
    """Every layer is a MoE layer, so ``layer`` is not read."""
    d, q, kv = m["d"], m["H"] * m["dh"], m["KV"] * m["dh"]
    f, e, fs = m["f"], m["E"], m["f"] * m["S"]
    ks = jax.random.split(key, 11)
    w = {"wq": _normal(ks[0], (d, q), d ** -0.5, dtype),
         "wk": _normal(ks[1], (d, kv), d ** -0.5, dtype),
         "wv": _normal(ks[2], (d, kv), d ** -0.5, dtype),
         "wo": _normal(ks[3], (q, d), q ** -0.5, dtype),
         "router": _normal(ks[4], (d, e), d ** -0.5, dtype),
         "moe_w_in": _normal(ks[5], (e, d, 2 * f), d ** -0.5, dtype),
         "moe_w_out": _normal(ks[6], (e, f, d), f ** -0.5, dtype),
         "sh_w_in": _normal(ks[7], (d, 2 * fs), d ** -0.5, dtype),
         "sh_w_out": _normal(ks[8], (fs, d), fs ** -0.5, dtype),
         "ln1": _normal(ks[9], (d,), NORM_SD, dtype),
         "ln2": _normal(ks[10], (d,), NORM_SD, dtype)}
    return w


def global_weights(m: dict, key, dtype=jnp.bfloat16) -> dict:
    ke, kn = jax.random.split(key)
    return {"embed": _normal(ke, (m["V"], m["d"]), m["d"] ** -0.5, dtype),
            "final_norm": _normal(kn, (m["d"],), NORM_SD, dtype)}


def program_params(conf: dict, seed: int):
    m = dims(conf)
    glob, layers = seed_keys(seed, m["L"])

    def build(gk, lks):
        g = global_weights(m, gk)
        blocks = jax.lax.map(lambda k: layer_weights(m, k, None), lks)
        return {"embed": g["embed"], "final_norm": g["final_norm"],
                "lm_head": g["embed"].T, "blocks": blocks}

    return jax.jit(build)(glob, jnp.stack(layers))


def token_matmul_flops(m: dict) -> float:
    """One token through every layer: attention projections, the
    router, its ``k`` experts and the shared ones."""
    d, q, kv, f = m["d"], m["H"] * m["dh"], m["KV"] * m["dh"], m["f"]
    ffn = d * m["E"] + 3 * d * f * (m["k"] + m["S"])
    return 2.0 * m["L"] * (d * (q + 2 * kv) + q * d + ffn)


def head_flops(m: dict) -> float:
    return 2.0 * m["d"] * m["V"]


def prompt_flops(m: dict, length: int, start: int = 0) -> float:
    keys = sum(start + i + 1 for i in range(length))
    return (length * token_matmul_flops(m)
            + 4.0 * m["L"] * m["H"] * m["dh"] * keys)


def generate_flops(m: dict, batch: int, prompt: int, new_tokens: int
                   ) -> float:
    per_row = prompt_flops(m, prompt) + head_flops(m)
    for i in range(new_tokens):
        per_row += prompt_flops(m, 1, prompt + i) + head_flops(m)
    return batch * per_row


def generate_lut(m: dict, batch: int, prompt: int, new_tokens: int,
                 site_bytes: dict) -> float:
    """Bytes of the ``expert`` site (``k`` experts' activations a token)
    and the ``mlp`` site (the shared experts'), in and out in bf16, plus
    each site's tables once per layer call."""
    tokens = batch * (prompt + new_tokens)
    elements = tokens * m["L"] * m["f"] * (m["k"] + m["S"])
    calls = m["L"] * (1 + new_tokens)
    return (4.0 * elements
            + (site_bytes["expert"] + site_bytes["mlp"]) * calls)
