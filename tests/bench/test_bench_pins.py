"""What the architecture modules must not move: the weights that
``program_params`` draws for ``data/configs/tiny.json`` at one seed, and
the plain reference's logits on them.  Both were recorded by the harness
as it stood before the dense code moved into
``bench/architectures/dense_decoder.py`` (``data/tiny_pins.json``,
``data/tiny_ref_logits.npy``); a change to the key derivation, the
layout or a scale reads as a different model."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

from harness import check, model  # noqa: E402

PINS = json.loads((DATA / "tiny_pins.json").read_text())
CONF = model.load_config("tiny", DATA)


@pytest.fixture(scope="module")
def arch():
    return model.load_architecture(CONF, BENCH)


def test_program_params_match_the_recorded_ones(arch):
    params = arch.program_params(CONF, PINS["seed"])
    leaves = {jax.tree_util.keystr(path): leaf for path, leaf
              in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(leaves) == set(PINS["params"])
    for name, pin in PINS["params"].items():
        leaf = leaves[name]
        assert (list(leaf.shape), str(leaf.dtype)) == (pin["shape"],
                                                       pin["dtype"]), name
        x = np.asarray(leaf.astype(jnp.float32), np.float64).ravel()
        # one bf16 step at most: the same draw, however the CPU rounds
        np.testing.assert_allclose(x[pin["at"]], pin["values"], rtol=2 ** -7,
                                   err_msg=name)
        assert np.abs(x).sum() == pytest.approx(pin["abs_sum"], rel=1e-4)
        assert x.sum() == pytest.approx(pin["sum"], rel=1e-3, abs=1e-3)


def test_reference_logits_match_the_recorded_ones(arch):
    m = arch.dims(CONF)
    ref = model.load_reference(CONF["reference"], BENCH)
    got = ref.logits_at(m, check.reference_weights(arch, m, PINS["seed"]),
                        np.asarray(PINS["tokens"], np.int32),
                        np.asarray(PINS["positions"], np.int32))
    want = np.load(DATA / "tiny_ref_logits.npy")
    assert got.shape == want.shape == (2, 2, m["V"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class _TwoKinds:
    """A stand-in architecture whose layer 0 differs from the rest."""

    @staticmethod
    def global_weights(m, key):
        return {"embed": jax.random.normal(key, (4, 2))}

    @staticmethod
    def layer_weights(m, key, layer):
        return {"w": jax.random.normal(key, (3 if layer == 0 else 2,))}


def test_reference_draws_compile_once_per_kind_of_layer():
    from harness import loops

    m = {"L": 5}
    _, layer_fn = check.reference_weights(_TwoKinds, m, 2 ** 33 + 1)
    counter = loops.CompileCounter()
    counter.start()
    got = [layer_fn(i)["w"] for i in range(m["L"])]
    counts = counter.stop()
    assert counts["compiled"] + counts["cache_loads"] == 2
    _, keys = model.seed_keys(2 ** 33 + 1, m["L"])
    for i, w in enumerate(got):
        want = jax.jit(lambda k, i=i: _TwoKinds.layer_weights(m, k, i))(keys[i])
        np.testing.assert_array_equal(w, want["w"])
