"""Decode-state (KV cache / recurrent state) construction per family.

Shapes are the serving memory contract; `cache_specs` provides
ShapeDtypeStructs for dry-run lowering and `cache_shardings` the placement:
full attention caches shard their *sequence* dim over the model axis (the
cache is the decode-memory hog — DESIGN.md SS4) and batch over data axes;
recurrent states are tiny and shard over batch only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


def _leaf(shape, dtype="bfloat16"):
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def cache_specs(cfg: ArchConfig, batch: int, max_seq: int,
                kv_dtype: str = "bfloat16"):
    """ShapeDtypeStruct pytree for the decode state.

    ``kv_dtype="int8"`` (decoder-only families) adds per-(pos, head) f32
    scales — the quantized-KV-cache serving mode."""
    kv, dh, L = cfg.n_kv_heads, cfg.d_head, cfg.n_layers
    if cfg.mla is not None:
        # the latent cache: kv_lora_rank + qk_rope_head_dim values a
        # token a layer; and the expert layers' assignment counts
        a = cfg.mla
        out = {"c_kv": _leaf((L, batch, max_seq, a.kv_lora_rank), kv_dtype),
               "k_pe": _leaf((L, batch, max_seq, a.qk_rope_head_dim),
                             kv_dtype)}
        if cfg.moe is not None:
            out["expert_counts"] = _leaf((cfg.moe.held + 1,), "int32")
        return out
    if cfg.family in ("dense", "moe", "vlm"):
        if kv_dtype == "int8":
            return {
                "k": _leaf((L, batch, max_seq, kv, dh), "int8"),
                "v": _leaf((L, batch, max_seq, kv, dh), "int8"),
                "k_scale": _leaf((L, batch, max_seq, kv), "float32"),
                "v_scale": _leaf((L, batch, max_seq, kv), "float32"),
            }
        return {
            "k": _leaf((L, batch, max_seq, kv, dh)),
            "v": _leaf((L, batch, max_seq, kv, dh)),
        }
    if cfg.family == "encdec":
        return {
            "k": _leaf((L, batch, max_seq, kv, dh)),
            "v": _leaf((L, batch, max_seq, kv, dh)),
            "xk": _leaf((L, batch, cfg.n_frames, kv, dh)),
            "xv": _leaf((L, batch, cfg.n_frames, kv, dh)),
        }
    if cfg.family == "ssm":
        d = cfg.d_model
        h = d // cfg.rwkv_head_dim
        n = cfg.rwkv_head_dim
        return {
            "att_x": _leaf((L, batch, 1, d)),
            "ffn_x": _leaf((L, batch, 1, d)),
            "wkv": _leaf((L, batch, h, n, n), "float32"),
        }
    if cfg.family == "hybrid":
        pattern = cfg.block_pattern or ("rec", "rec", "attn")
        g = cfg.n_layers // len(pattern)
        n_tail = cfg.n_layers - g * len(pattern)
        drnn = cfg.d_rnn or cfg.d_model
        w = cfg.local_window

        def rec_state(lead):
            return {
                "conv": _leaf(lead + (batch, cfg.conv_width - 1, drnn)),
                "lru": _leaf(lead + (batch, drnn), "float32"),
            }

        groups = {}
        for i, kind in enumerate(pattern):
            if kind == "rec":
                groups[f"t{i}"] = rec_state((g,))
            else:
                groups[f"t{i}"] = {
                    "k": _leaf((g, batch, w, kv, dh)),
                    "v": _leaf((g, batch, w, kv, dh)),
                }
        out = {"groups": groups, "tail": {}}
        for i in range(n_tail):
            out["tail"][f"t{i}"] = rec_state(())
        return out
    raise ValueError(cfg.family)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               kv_dtype: str = "bfloat16"):
    """Zero-initialized decode state (concrete arrays)."""
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        cache_specs(cfg, batch, max_seq, kv_dtype)
    )


def cache_shardings(cfg: ArchConfig, mesh, batch: int, max_seq: int,
                    kv_dtype: str = "bfloat16"):
    """NamedSharding pytree matching :func:`cache_specs`.

    Full-attention K/V caches: batch over dp, sequence over tp (the decode
    memory hog gets 1/(dp*tp) per device).  Recurrent states: batch over
    dp, channel dims over tp where divisible.
    """
    from repro.nn.sharding import named_sharding

    specs = cache_specs(cfg, batch, max_seq, kv_dtype)

    def assign(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        if name in ("k", "v", "xk", "xv"):      # (L|G, B, T, KV, Dh)
            axes = (None, "dp", "tp", None, None)
        elif name in ("k_scale", "v_scale"):     # (L, B, T, KV)
            axes = (None, "dp", "tp", None)
        elif name == "wkv":                      # (L, B, H, N, N)
            axes = (None, "dp", None, None, None)
        elif name in ("att_x", "ffn_x"):         # (L, B, 1, d)
            axes = (None, "dp", None, "tp")
        elif name == "conv":                     # (..., B, K-1, drnn)
            axes = (None,) * (nd - 3) + ("dp", None, "tp")
        elif name == "lru":                      # (..., B, drnn)
            axes = (None,) * (nd - 2) + ("dp", "tp")
        else:
            axes = (None,) * nd
        return named_sharding(mesh, *axes[:nd], shape=leaf.shape)

    return jax.tree_util.tree_map_with_path(assign, specs)
