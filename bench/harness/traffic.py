"""The one traffic generator: reads a mix's parameters, draws from a seed.

Two kinds of mix, named by the file's ``kind``:

* ``offline`` -- batches of ``batch`` prompts of ``prompt_len`` tokens,
  ``new_tokens`` to generate; call ``i`` of a run draws its own tokens
  from ``(seed, i)``, so every call is fresh and the shapes never change.
* ``open_loop`` -- independent users arriving at ``rate_per_s``.  Every
  seed gets the same set of inter-arrival gaps, prompt lengths and
  output lengths (stratified quantiles of the stated distributions), in
  an order and with token ids of its own; so a seed changes which
  request comes when, not how much work a window holds.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

BENCH = Path(__file__).resolve().parents[1]


def load_traffic(name: str, root: Path = BENCH) -> dict:
    mix = json.loads((root / "traffic" / f"{name}.json").read_text())
    if mix.get("kind") not in ("offline", "open_loop"):
        raise ValueError(f"traffic {name}: unknown kind {mix.get('kind')!r}")
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (2 ** 63), *(s % (2 ** 32) for s in stream)])


def offline_batch(mix: dict, vocab: int, seed: int, call: int) -> dict:
    """Call ``call``'s prompts, (batch, prompt_len) int32 ids in
    [1, vocab)."""
    rng = _rng(seed, 1, call)
    return {"tokens": rng.integers(1, vocab, (mix["batch"],
                                              mix["prompt_len"]),
                                   dtype=np.int32)}


def calibration_batch(mix: dict, vocab: int, seed: int) -> dict:
    cal = mix["calibration"]
    rng = _rng(seed, 2)
    return {"tokens": rng.integers(1, vocab, (cal["batch"], cal["seq_len"]),
                                   dtype=np.int32)}


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal, rounded up to
    ``multiple``."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    mult = spec.get("multiple", 1)
    x = np.ceil(x / mult) * mult
    return np.clip(x, spec["min"], spec["max"]).astype(int)


@dataclasses.dataclass
class Arrival:
    rid: int
    due_s: float            # on the window's clock
    prompt: list
    max_new: int


def open_loop(mix: dict, vocab: int, seed: int, seconds: float,
              rate: float | None = None) -> list[Arrival]:
    """Requests due in ``[0, seconds)`` at ``rate`` (the mix's own rate
    by default), in due order."""
    rate = mix["rate_per_s"] if rate is None else rate
    n = int(math.floor(rate * seconds))
    if n < 1:
        return []
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    gaps = -np.log1p(-_quantiles(n))
    gaps *= (1.0 / rate) / gaps.mean()          # mean gap is exactly 1/rate
    prompts = _lengths(mix["prompt_len"], n)
    outs = _lengths(mix["output_len"], n)
    rng = _rng(seed, 3)
    gaps, prompts, outs = (a[rng.permutation(n)] for a in (gaps, prompts,
                                                           outs))
    due = np.cumsum(gaps) - gaps[0]             # the first is due at 0
    due *= min(1.0, 0.999 * seconds / max(due[-1], 1e-9))
    ids = _rng(seed, 4)
    return [Arrival(rid=i, due_s=float(due[i]),
                    prompt=[int(t) for t in ids.integers(1, vocab,
                                                         int(prompts[i]))],
                    max_new=int(outs[i]))
            for i in range(n)]


def distinct_prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can draw (the replay programs to warm
    up)."""
    spec = mix["prompt_len"]
    mult = spec.get("multiple", 1)
    lo = int(math.ceil(spec["min"] / mult) * mult)
    return list(range(lo, int(spec["max"]) + 1, mult))
