"""The dense path's programs are what they were before latent attention,
YaRN, leading dense layers and the held expert layer came into the
program: the prefill and decode programs of ``data/configs/tiny.json``,
served with its stacked Pallas tables as the harness builds them, lower
on the CPU to the same StableHLO text, byte for byte.  The digests were
recorded from the tree before those changes; a change to this text is a
change to every dense cell's programs and has to be explained."""
from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT / "bench"))

from harness import model, system, traffic  # noqa: E402

RECORDED = {
    "prefill": ("70cb3e70e0fdba2443b482e0159dbbc9"
                "a051b3f9b12dedb41a7557ff81677677", 103869),
    "decode": ("48203691569657cb0a88824d1b984a40"
               "9424935a6d1f1dec42ad2e3c914591ee", 104885),
}


def test_dense_programs_lower_to_the_recorded_text():
    from repro.serve.decode import decode_step, prefill

    conf = model.load_config("tiny", DATA)
    arch = model.load_architecture(conf, ROOT / "bench")
    params = arch.program_params(conf, 7)
    mix = traffic.load_traffic("offline", DATA)
    cfg, tables, _, _ = system.calibrate(
        arch.arch_config(conf), params,
        traffic.calibration_batch(mix, 512, 7))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}

    # lambdas, as generate() lowers them: the module's name is in the text
    pre = lambda p, x: prefill(p, cfg, x, max_seq=12,  # noqa: E731
                               lut_tables=tables)
    _, cache = jax.eval_shape(pre, params, batch)
    texts = {
        "prefill": jax.jit(pre).lower(params, batch).as_text(),
        "decode": jax.jit(lambda p, c, tk, pos: decode_step(
            p, cfg, c, tk, pos, lut_tables=tables)).lower(
            params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32),
            np.int32(8)).as_text(),
    }
    got = {k: (hashlib.sha256(t.encode()).hexdigest(), len(t))
           for k, t in texts.items()}
    assert got == RECORDED
