"""A configuration file, the seed's keys, and the modules it names.

The configuration file (``configs/<name>.json``) holds the published
config under ``config`` with every departure listed in ``reduced``, and
names two modules of the benchmark's own:

* ``"architecture"``: ``architectures/<name>.py``, which knows the
  layout: the sizes (:data:`INTERFACE`'s ``dims``), the program's
  ``ArchConfig``, the weights drawn from the seed, and the work a call
  has to do;
* ``"reference"``: ``references/<name>.py``, the plain float32 model,
  ``logits_at(m, weights, tokens, positions, quant=None)``.

Both are loaded from the cell's own bench directory, so a new
architecture is new files only.  What every architecture shares is
here: how a seed becomes keys, and the normal draw of one weight.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
NORM_SD = 0.1      # RMSNorm weights are 1 + N(0, NORM_SD^2)

# What an architecture module defines.  ``dims(conf)`` gives at least
# ``L`` (layers) and ``V`` (vocabulary); the rest take its result ``m``:
# ``arch_config(conf)``, ``global_weights(m, key)``,
# ``layer_weights(m, key, layer)``, ``program_params(conf, seed)``, and
# the work counts ``prompt_flops(m, length, start=0)``, ``head_flops(m)``,
# ``generate_flops(m, batch, prompt, new_tokens)`` and
# ``generate_lut(m, batch, prompt, new_tokens, site_bytes)``.
INTERFACE = ("dims", "arch_config", "global_weights", "layer_weights",
             "program_params", "prompt_flops", "head_flops",
             "generate_flops", "generate_lut")


def load_config(name: str, root: Path = BENCH) -> dict:
    conf = json.loads((root / "configs" / f"{name}.json").read_text())
    for key in ("architecture", "reference"):
        if not conf.get(key):
            raise ValueError(f"configuration {name}: no {key!r} module "
                             f"named")
    return conf


def _normal(key, shape, sd, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * sd).astype(dtype)


def seed_keys(seed: int, n_layers: int):
    """(global key, per-layer keys) for a run's seed, which may exceed
    32 bits."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 32)),
                              seed >> 32)
    glob = jax.random.fold_in(base, 1 << 20)
    layers = [jax.random.fold_in(base, i) for i in range(n_layers)]
    return glob, layers


@functools.lru_cache(maxsize=None)
def _load(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_architecture(conf: dict, bench: Path):
    """The architecture module ``architectures/<conf["architecture"]>.py``
    of ``bench``; one that lacks part of :data:`INTERFACE` is an error."""
    mod = _load(Path(bench).resolve() / "architectures"
                / f"{conf['architecture']}.py", "bench_arch")
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise TypeError(f"architecture {conf['architecture']}: no "
                        f"{', '.join(missing)}")
    return mod


def load_reference(name: str, bench: Path):
    """The plain reference module ``references/<name>.py`` of ``bench``."""
    return _load(Path(bench).resolve() / "references" / f"{name}.py",
                 "bench_ref")
