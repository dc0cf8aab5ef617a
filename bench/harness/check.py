"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample
of what the window served (drawn from the seed, the longest request
always in it) goes through the plain float32 reference, teacher-forced
on the served tokens.  For each served token, its *gap* is how far the
reference's logit for it lies below the reference's best logit at that
position.  The number compared is the widest gap over the sample; its
limit is in ``limits/<cell>.json``, with the readings it was set from.
A limits file names each number it holds a cell to, so a cell whose
widest gap cannot separate sound runs from the control (a router's
near-ties flip an expert) is held to another of :func:`compare`'s
numbers by its own file.

The control (``--readings`` only) puts the reference computed with fp8
projections in the program's place: at the same positions of the same
prompts and served tokens, the gap of the token it ranks first.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import numpy as np

from . import model, traffic

NUMBERS = ("max_logit_gap",)


def reference_weights(arch, m: dict, seed: int):
    """``(global_fn, layer_fn)`` drawing the run's weights from the seed
    again, layer by layer, with the architecture module's own generator.
    ``layer_fn(i)`` tells the generator the layer as a Python int; the
    layers whose draw traces to the same program share one compiled
    draw, so a stack of like layers compiles once."""
    glob, layers = model.seed_keys(seed, m["L"])
    g = jax.jit(functools.partial(arch.global_weights, m))
    draws = {}

    def layer_fn(i):
        draw = functools.partial(arch.layer_weights, m, layer=i)
        program = str(jax.make_jaxpr(draw)(layers[i]))
        if program not in draws:
            draws[program] = jax.jit(draw)
        return draws[program](layers[i])

    return (lambda: g(glob)), layer_fn


def gaps(ref: np.ndarray, served: np.ndarray) -> np.ndarray:
    """(N, K) gaps of ``served`` tokens under reference logits (N, K, V)."""
    picked = np.take_along_axis(ref, served[..., None], -1)[..., 0]
    return ref.max(-1) - picked


def offline_sample(calls, mix: dict, vocab: int, seed: int):
    """``(tokens, positions, served)`` of ``check.calls`` window calls
    drawn from the seed."""
    k = min(len(calls), int(mix.get("check", {}).get("calls", 1)))
    pick = sorted(traffic._rng(seed, 5).choice(len(calls), k, replace=False))
    toks, pos, served = [], [], []
    for i in pick:
        c = calls[int(i)]
        prompt = traffic.offline_batch(mix, vocab, seed, c.index)["tokens"]
        out = np.asarray(c.tokens, np.int32)
        t = prompt.shape[1]
        toks.append(np.concatenate([prompt, out[:, :-1]], 1))
        pos.append(np.broadcast_to(t - 1 + np.arange(out.shape[1]),
                                   out.shape))
        served.append(out)
    return (np.concatenate(toks), np.concatenate(pos).astype(np.int32),
            np.concatenate(served))


def open_loop_sample(served_list, mix: dict, seed: int):
    """``(tokens, positions, served)`` of ``check.requests`` finished
    requests drawn from the seed, the longest always among them; each
    padded to the cache length, so the reference has one shape."""
    done = [s for s in served_list if s.done and s.req.out]
    if not done:
        return None
    n = min(len(done), int(mix.get("check", {}).get("requests", 12)))
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].req.prompt) + len(done[i].req.out))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + list(traffic._rng(seed, 6).choice(
        rest, n - 1, replace=False)) if n > 1 else [longest]
    t = mix["max_seq"]
    kmax = max(len(done[int(i)].req.out) for i in pick)
    toks = np.zeros((n, t), np.int32)
    pos = np.zeros((n, kmax), np.int32)
    served = np.zeros((n, kmax), np.int32)
    valid = np.zeros((n, kmax), bool)
    for r, i in enumerate(pick):
        req = done[int(i)].req
        seq = list(req.prompt) + list(req.out[:-1])
        toks[r, :len(seq)] = seq
        k = len(req.out)
        pos[r, :k] = len(req.prompt) - 1 + np.arange(k)
        pos[r, k:] = pos[r, k - 1]
        served[r, :k] = req.out
        served[r, k:] = req.out[-1]
        valid[r, :k] = True
    return toks, pos, served, valid


def compare(conf: dict, bench: Path, seed: int, sample, *,
            control: bool = False) -> dict:
    """The numbers compared, for the program (and the control); the
    architecture and reference modules come from ``bench``."""
    tokens, positions, served = sample[:3]
    valid = sample[3] if len(sample) > 3 else np.ones(served.shape, bool)
    arch = model.load_architecture(conf, bench)
    m = arch.dims(conf)
    ref_mod = model.load_reference(conf["reference"], bench)
    w = reference_weights(arch, m, seed)
    ref = ref_mod.logits_at(m, w, tokens, positions)
    g = gaps(ref, served)[valid]
    out = {"max_logit_gap": float(g.max()),
           "mean_logit_gap": float(g.mean()),
           "served_tokens": int(valid.sum()),
           "top1_agree": float((g == 0).mean())}
    if control:
        ctl = ref_mod.logits_at(m, w, tokens, positions, quant="fp8")
        cg = gaps(ref, ctl.argmax(-1).astype(np.int32))[valid]
        out.update(control_max_logit_gap=float(cg.max()),
                   control_mean_logit_gap=float(cg.mean()),
                   control_top1_agree=float((cg == 0).mean()))
    return out


def load_limits(bench: Path, cell: str) -> dict | None:
    path = bench / "limits" / f"{cell}.json"
    if not path.exists():
        return None
    return json.loads(path.read_text())


def judge(numbers: dict, limits: dict | None) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` for each number the
    limits file names (:data:`NUMBERS` where there is no file); no
    limits, or a number missing, is not correct."""
    checks = {}
    ok = bool(limits)
    for name in limits or NUMBERS:
        lim = limits[name]["limit"] if limits else None
        val = numbers.get(name)
        checks[name] = {"value": val, "limit": lim}
        ok = ok and val is not None and lim is not None and val <= lim
    return ok, checks
