"""The dense architecture's FLOP and byte counts
(``bench/architectures/dense_decoder.py``) against hand counts at
qwen3-0.6b widths."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from harness import model  # noqa: E402

CONF = model.load_config("qwen3-0.6b", BENCH)
work = model.load_architecture(CONF, BENCH)
M = work.dims(CONF)


def test_dims_of_the_config():
    assert (M["L"], M["d"], M["H"], M["KV"], M["dh"], M["ff"], M["V"]) == (
        28, 1024, 16, 8, 128, 3072, 151936)
    assert M["qk_norm"] and M["tied"] and M["rot"] == 128


def test_token_matmul_flops_by_hand():
    # q 1024x2048, k and v 1024x1024 each, o 2048x1024, gate/up/down
    # 1024x3072 each: 2 FLOPs per multiply-add, 28 layers
    per_layer = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                 + 3 * 1024 * 3072)
    assert work.token_matmul_flops(M) == 2 * 28 * per_layer == 880803840


def test_attention_and_head_by_hand():
    assert work.attn_flops(M, 10) == 4 * 28 * 16 * 128 * 10
    assert work.head_flops(M) == 2 * 1024 * 151936
    # 3 tokens causally: 1 + 2 + 3 keys
    assert work.prompt_flops(M, 3) == (3 * work.token_matmul_flops(M)
                                       + work.attn_flops(M, 6))
    assert work.prompt_flops(M, 1, start=9) == (
        work.token_matmul_flops(M) + work.attn_flops(M, 10))


def test_generate_flops_prefill_heavy():
    b, t, n = 8, 2048, 16
    got = work.generate_flops(M, b, t, n)
    keys = t * (t + 1) // 2 + sum(t + i + 1 for i in range(n))
    want = b * ((t + n) * work.token_matmul_flops(M)
                + work.attn_flops(M, keys) + (1 + n) * work.head_flops(M))
    assert got == pytest.approx(want, rel=1e-12)
    # projections 14.5 TFLOP, causal attention 3.9 TFLOP for the call
    assert 1.84e13 < got < 1.86e13


def test_lut_bytes_by_hand():
    # 8 x 2064 tokens x 3072 elements x 28 layers, 2 B in + 2 B out, and
    # one layer's tables (1000 B) per layer call: 28 x 17 calls
    got = work.generate_lut(M, 8, 2048, 16, {"mlp": 1000.0})
    assert got == 4 * 8 * 2064 * 3072 * 28 + 1000 * 28 * 17
    # the dense decoder serves the MLP site alone; another site's bytes
    # are not its work
    assert work.generate_lut(M, 8, 2048, 16,
                             {"mlp": 1000.0, "expert": 7.0}) == got
