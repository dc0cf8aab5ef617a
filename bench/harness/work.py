"""The work a call has to do, counted from the model's shapes.

Counts follow the model, not the implementation, so a later kernel or
layout is measured against the same work:

* matmul FLOPs: 2 per multiply-add of every projection of a token
  (q, k, v, o; gate, up, down);
* attention FLOPs: ``4 * heads * head_dim`` per key a query attends
  (scores and the weighted sum), over the keys causally visible;
* the output head, ``2 * d_model * vocab``, only where logits are used;
* the LUT site: each MLP activation element read and written at the
  served dtype, plus one layer's served table bytes per layer call.

``m`` is :func:`harness.model.dims` of the configuration.
"""
from __future__ import annotations


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
    """HBM bytes the MLP LUT site must move: ``elements`` activations in
    and out, and one layer's tables per layer call."""
    return 2.0 * dtype_bytes * elements + table_bytes_per_layer * layer_calls


def generate_lut(m: dict, batch: int, prompt: int, new_tokens: int,
                 table_bytes_per_layer: float) -> float:
    """LUT bytes of one ``generate`` call (prefill + decode steps)."""
    tokens = batch * (prompt + new_tokens)
    calls = m["L"] * (1 + new_tokens)
    return lut_bytes(m, tokens * m["ff"] * m["L"], table_bytes_per_layer,
                     calls)
