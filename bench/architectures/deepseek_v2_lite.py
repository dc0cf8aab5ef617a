"""DeepSeek-V2 (-Lite): multi-head latent attention with YaRN rotary,
``first_k_dense_replace`` leading dense layers, then expert layers that
route over ``router_experts`` experts and hold ``n_routed_experts`` of
them (the chip's share of an expert-parallel deployment) beside
``n_shared_experts`` always-on ones: its sizes, the program's
``ArchConfig``, its weights from the seed, and the work a call has to do.

Weights are drawn in the program's layout, one layer from
``fold_in(seed key, layer)``: fused gate|up projections (the gate
first), RMSNorm weights stored as offsets from 1, ``wkv_b`` with each
head's ``[k_nope ; v]`` side by side.  A dense layer and an expert layer
have different trees, so :func:`layer_weights` reads ``layer``.

Work is counted from the model's shapes, as ``dense_decoder.py`` does:

* matmul FLOPs, 2 per multiply-add, of the model's own projections
  (q, the latent and rotary key ``W_kv_a``, the up-projection ``W_kv_b``,
  o; the dense MLP; the router, the routed experts and the shared ones),
  whatever form the program computes them in;
* attention FLOPs: ``2 * heads * (qk_head_dim + v_head_dim)`` per key a
  query attends, over the keys causally visible;
* in each expert layer, the held share of the top-k: ``k * held / E``
  expert-tokens a token (0.75 for 6 of 64 with 8 held), the expectation
  over tokens of a router with no preference;
* the output head, ``2 * d_model * vocab``, only where logits are used;
* the LUT sites: each activation element of the ``expert`` site (the
  held share of the routed experts) and the ``mlp`` site (the leading
  dense layers, the shared experts) read and written in bf16, plus each
  site's served tables of one hosting layer per layer call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.model import NORM_SD, _normal, seed_keys


def dims(conf: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file, which
    holds the published config's keys at its top level."""
    c, dep = conf, conf["deployment"]
    rs = c["rope_scaling"]
    if (c["hidden_act"] != "silu" or c["scoring_func"] != "softmax"
            or c["topk_method"] != "greedy" or c["n_group"] != 1
            or c["routed_scaling_factor"] != 1 or c["q_lora_rank"]
            or c["moe_layer_freq"] != 1 or rs["type"] != "yarn"):
        raise ValueError(f"{conf['name']}: a DeepSeek-V2 variant this "
                         f"module does not describe")
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "H": c["num_attention_heads"], "R": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "ff": c["intermediate_size"],
            "f": c["moe_intermediate_size"], "E": dep["router_experts"],
            "held": c["n_routed_experts"], "first": dep["first_expert"],
            "k": c["num_experts_per_tok"], "S": c["n_shared_experts"],
            "dense": c["first_k_dense_replace"],
            "norm_topk": bool(c["norm_topk_prob"]), "V": c["vocab_size"],
            "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
            "factor": float(rs["factor"]), "mscale": float(rs["mscale"]),
            "mscale_all": float(rs["mscale_all_dim"]),
            "beta_fast": float(rs["beta_fast"]),
            "beta_slow": float(rs["beta_slow"]),
            "orig_ctx": int(rs["original_max_position_embeddings"])}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                    YarnScaling)

    m = dims(conf)
    serving = conf.get("serving", {})
    return ArchConfig(
        name=conf["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["H"], d_head=m["nope"] + m["rope"],
        d_ff=m["f"], vocab_size=m["V"], activation="swiglu",
        rope_theta=m["theta"], norm_eps=m["eps"],
        dtype=conf.get("torch_dtype", "bfloat16"),
        mla=MLAConfig(kv_lora_rank=m["R"], qk_nope_head_dim=m["nope"],
                      qk_rope_head_dim=m["rope"], v_head_dim=m["dv"]),
        rope_scaling=YarnScaling(
            factor=m["factor"], original_max_position_embeddings=m["orig_ctx"],
            beta_fast=m["beta_fast"], beta_slow=m["beta_slow"],
            mscale=m["mscale"], mscale_all_dim=m["mscale_all"]),
        first_k_dense=m["dense"], d_ff_dense=m["ff"],
        moe=MoEConfig(n_experts=m["E"], top_k=m["k"], d_expert=m["f"],
                      n_shared=m["S"], first_expert=m["first"],
                      n_held=m["held"], norm_topk_prob=m["norm_topk"]),
        lut_act_bits_in=serving.get("lut_act_bits_in", 10),
        lut_act_bits_out=serving.get("lut_act_bits_out", 10))


def layer_weights(m: dict, key, layer, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer`` (a Python int): latent attention, then a dense MLP
    (the first ``dense`` layers) or the held experts, the router and the
    shared experts."""
    d, h, r = m["d"], m["H"], m["R"]
    ks = jax.random.split(key, 12)
    w = {"wq": _normal(ks[0], (d, h * (m["nope"] + m["rope"])), d ** -0.5,
                       dtype),
         "wkv_a": _normal(ks[1], (d, r + m["rope"]), d ** -0.5, dtype),
         "kv_norm": _normal(ks[2], (r,), NORM_SD, dtype),
         "wkv_b": _normal(ks[3], (r, h * (m["nope"] + m["dv"])), r ** -0.5,
                          dtype),
         "wo": _normal(ks[4], (h * m["dv"], d), (h * m["dv"]) ** -0.5, dtype),
         "ln1": _normal(ks[5], (d,), NORM_SD, dtype),
         "ln2": _normal(ks[6], (d,), NORM_SD, dtype)}
    if layer < m["dense"]:
        w["w_in"] = _normal(ks[7], (d, 2 * m["ff"]), d ** -0.5, dtype)
        w["w_out"] = _normal(ks[8], (m["ff"], d), m["ff"] ** -0.5, dtype)
        return w
    f, fs = m["f"], m["f"] * m["S"]
    w["router"] = _normal(ks[7], (d, m["E"]), d ** -0.5, dtype)
    w["moe_w_in"] = _normal(ks[8], (m["held"], d, 2 * f), d ** -0.5, dtype)
    w["moe_w_out"] = _normal(ks[9], (m["held"], f, d), f ** -0.5, dtype)
    w["sh_w_in"] = _normal(ks[10], (d, 2 * fs), d ** -0.5, dtype)
    w["sh_w_out"] = _normal(ks[11], (fs, d), fs ** -0.5, dtype)
    return w


def global_weights(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """The embedding, the final norm and the output head (untied)."""
    ke, kn, kh = jax.random.split(key, 3)
    return {"embed": _normal(ke, (m["V"], m["d"]), m["d"] ** -0.5, dtype),
            "final_norm": _normal(kn, (m["d"],), NORM_SD, dtype),
            "lm_head": _normal(kh, (m["d"], m["V"]), m["d"] ** -0.5, dtype)}


def program_params(conf: dict, seed: int):
    """The program's parameter tree, on the device, from one jitted
    call: the leading dense layers under ``lead``, the expert layers
    under ``blocks``."""
    m = dims(conf)
    glob, layers = seed_keys(seed, m["L"])
    k = m["dense"]

    def build(gk, lks):
        tree = global_weights(m, gk)
        tree["lead"] = jax.lax.map(lambda key: layer_weights(m, key, 0),
                                   lks[:k])
        tree["blocks"] = jax.lax.map(lambda key: layer_weights(m, key, k),
                                     lks[k:])
        return tree

    return jax.jit(build)(glob, jnp.stack(layers))


# =========================================================================
# work counts
# =========================================================================
def routed_share(m: dict) -> float:
    """Expert-tokens a token computes here in one expert layer."""
    return m["k"] * m["held"] / m["E"]


def token_matmul_flops(m: dict) -> float:
    """One token through every layer's projections."""
    d, h = m["d"], m["H"]
    attn = (d * h * (m["nope"] + m["rope"]) + d * (m["R"] + m["rope"])
            + m["R"] * h * (m["nope"] + m["dv"]) + h * m["dv"] * d)
    dense = 3 * d * m["ff"]
    moe = d * m["E"] + 3 * d * m["f"] * (routed_share(m) + m["S"])
    n_moe = m["L"] - m["dense"]
    return 2.0 * (m["L"] * attn + m["dense"] * dense + n_moe * moe)


def attn_flops(m: dict, keys: float) -> float:
    """One query attending ``keys`` keys in every layer."""
    return 2.0 * m["L"] * m["H"] * (m["nope"] + m["rope"] + m["dv"]) * keys


def head_flops(m: dict) -> float:
    return 2.0 * m["d"] * m["V"]


def prompt_flops(m: dict, length: int, start: int = 0) -> float:
    """Ingesting ``length`` tokens at positions ``start..`` of one
    sequence, without the output head."""
    keys = length * start + length * (length + 1) / 2
    return length * token_matmul_flops(m) + attn_flops(m, keys)


def generate_flops(m: dict, batch: int, prompt: int, new_tokens: int
                   ) -> float:
    """One offline ``generate`` call: prefill with the head at the last
    position, then ``new_tokens`` decode steps each with the head."""
    per_row = prompt_flops(m, prompt) + head_flops(m)
    for i in range(new_tokens):
        per_row += prompt_flops(m, 1, prompt + i) + head_flops(m)
    return batch * per_row


def generate_lut(m: dict, batch: int, prompt: int, new_tokens: int,
                 site_bytes: dict) -> float:
    """LUT bytes of one ``generate`` call: the ``expert`` site's elements
    (the held share of the routed experts in each expert layer) and the
    ``mlp`` site's (the leading dense layers' MLP, the expert layers'
    shared experts), in and out in bf16, plus each site's tables of one
    hosting layer per layer call."""
    tokens = batch * (prompt + new_tokens)
    n_moe = m["L"] - m["dense"]
    expert = tokens * n_moe * m["f"] * routed_share(m)
    mlp = tokens * (m["dense"] * m["ff"] + n_moe * m["f"] * m["S"])
    calls = 1 + new_tokens
    return (4.0 * (expert + mlp)
            + (site_bytes["expert"] * n_moe + site_bytes["mlp"] * m["L"])
            * calls)
