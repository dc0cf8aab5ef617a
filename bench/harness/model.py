"""A configuration file, its weights from the seed, and its reference.

The configuration file (``configs/<name>.json``) holds the published
config under ``config`` with every departure listed in ``reduced``.
:func:`arch_config` maps it onto the program's ``ArchConfig``.

Weights are the benchmark's, not the program's: :func:`layer_weights`
draws one layer from ``fold_in(seed key, layer)`` in the published
layout (separate gate and up projections, RMSNorm weights), and
:func:`program_params` lays the same numbers out as the program's
parameter tree in one jitted call.  The plain reference draws the same
layers again, one at a time, so it takes nothing the program made.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
NORM_SD = 0.1      # RMSNorm weights are 1 + N(0, NORM_SD^2)


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


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


def _normal(key, shape, sd, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * sd).astype(dtype)


def layer_weights(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """One decoder layer in the published layout.  ``w_in`` is the gate
    and up projections side by side, ``[:, :ff]`` the gate."""
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


def seed_keys(seed: int, n_layers: int):
    """(global key, per-layer keys) for a run's seed, which may exceed
    32 bits."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 32)),
                              seed >> 32)
    glob = jax.random.fold_in(base, 1 << 20)
    layers = [jax.random.fold_in(base, i) for i in range(n_layers)]
    return glob, layers


def program_params(conf: dict, seed: int):
    """The program's parameter tree, on the device, from one jitted
    call.  The output head is the embedding's transpose (tied)."""
    m = dims(conf)
    glob, layers = seed_keys(seed, m["L"])

    def build(gk, lks):
        g = global_weights(m, gk)
        blocks = jax.lax.map(lambda k: layer_weights(m, k), lks)
        return {"embed": g["embed"], "final_norm": g["final_norm"],
                "lm_head": g["embed"].T, "blocks": blocks}

    return jax.jit(build)(glob, jnp.stack(layers))


def load_reference(name: str, root: Path = BENCH):
    """The plain reference module ``references/<name>.py``."""
    path = root / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
