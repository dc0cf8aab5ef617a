"""Streaming per-site activation capture (paper SS4.1, serving-side).

The paper injects don't cares for input patterns *unobserved in the
training data*.  For the LM serving stack the analogous signal is the
per-activation-site input distribution: every layer's nonlinearity sees a
different distribution, so every (layer, site) pair earns its own
observed-bin mask — the freedom the compressor exploits per table.

This module is the front end of that pipeline:

1. :class:`ActivationCapture` — a context manager that, while active,
   makes every ``repro.nn.mlp.make_activation`` call site stream its
   pre-activation inputs into a per-site histogram (one ``2**w_in``-bin
   count vector per ``L{layer}/{site}`` key) and its post-activation
   outputs into a streaming ``[y_lo, y_hi]`` range tracker (the signal
   per-site output-width selection prices, :mod:`repro.tune.sweep`).
   Accumulation is host-side numpy; traced values reach the host through
   ``jax.debug.callback``, so capture is jit-/scan-safe, and concrete
   (eager) values take a direct path.
2. Layer identity — while a capture is active the layer stacks unroll
   (``repro.nn.mlp.run_layers``) so each call site knows its layer index;
   every family's decoder stack routes through ``run_layers`` (encdec
   included), so all six families capture per-layer keys.  Loops outside
   ``run_layers`` (the encdec *encoder*) fall back to a site-level
   histogram, which per-layer keys shadow at mask resolution.
3. :func:`capture_model` — two-pass eval driver: stream calibration
   batches through the exact (non-LUT) forward of any architecture family
   and return the filled capture.  Masks/smoothing live in
   :mod:`repro.calib.masks`; persistence in :mod:`repro.calib.store`.
"""
from __future__ import annotations

import numpy as np

import jax

from repro import sites

# Active captures, innermost last.  JAX tracing is single-threaded per
# process and capture is an eval-time tool, so a plain module-level stack
# (rather than a contextvar) is sufficient and keeps the hot check cheap.
_STACK: list["ActivationCapture"] = []


def capture_active() -> bool:
    """True while any :class:`ActivationCapture` context is entered."""
    return bool(_STACK)


def current() -> "ActivationCapture | None":
    return _STACK[-1] if _STACK else None


def site_key(site: str, layer: int | None = None) -> str:
    """Canonical per-site key: ``"L{layer}/{site}"``, or the bare site kind
    when no layer identity is available.  Matches the ``TableSpec`` names
    :func:`repro.serve.plans.build_serving_plans` assigns."""
    return site if layer is None else f"L{layer}/{site}"


class ActivationCapture:
    """Streaming observed-bin histogram accumulator.

    Bins follow the LUT activation's input quantizer exactly (uniform
    ``2**w_in`` grid over ``[x_lo, x_hi]``, round-to-nearest, clipped), so
    a bin with zero observations is precisely an input code the served
    table would never be asked for — a don't care.
    """

    def __init__(self, w_in: int = 10, x_lo: float = -8.0,
                 x_hi: float = 8.0):
        if x_hi <= x_lo:
            raise ValueError(
                f"ActivationCapture: empty input range "
                f"[x_lo={x_lo}, x_hi={x_hi}]")
        self.w_in = w_in
        self.x_lo = float(x_lo)
        self.x_hi = float(x_hi)
        # Per-key input-domain overrides (registry sites pin their own
        # quantizer range, e.g. the softmax exp over [-16, 0]); keys
        # without an entry histogram over the global [x_lo, x_hi].
        self.domains: dict[str, tuple[float, float]] = {}
        self.hists: dict[str, np.ndarray] = {}
        # Streaming per-site *output* range: key -> [y_lo, y_hi] float64.
        # The observed output span is what per-site w_out selection prices
        # (a site whose outputs occupy a fraction of the activation's full
        # range needs fewer output bits at the same resolution).
        self.ranges: dict[str, np.ndarray] = {}
        self.n_batches = 0
        self.n_samples = 0

    # -- context management ------------------------------------------------
    def __enter__(self) -> "ActivationCapture":
        _STACK.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _STACK.remove(self)

    # -- accumulation ------------------------------------------------------
    def _accum(self, key: str, x: np.ndarray) -> None:
        flat = np.asarray(x, dtype=np.float64).reshape(-1)
        flat = flat[np.isfinite(flat)]
        if flat.size == 0:
            return
        levels = (1 << self.w_in) - 1
        x_lo, x_hi = self.domains.get(key, (self.x_lo, self.x_hi))
        xn = np.clip((flat - x_lo) / (x_hi - x_lo), 0.0, 1.0)
        codes = np.rint(xn * levels).astype(np.int64)
        hist = self.hists.get(key)
        if hist is None:
            hist = self.hists.setdefault(
                key, np.zeros(1 << self.w_in, dtype=np.int64))
        hist += np.bincount(codes, minlength=1 << self.w_in)
        self.n_samples += flat.size

    def _accum_out(self, key: str, y: np.ndarray) -> None:
        flat = np.asarray(y, dtype=np.float64).reshape(-1)
        flat = flat[np.isfinite(flat)]
        if flat.size == 0:
            return
        r = self.ranges.get(key)
        if r is None:
            r = self.ranges.setdefault(
                key, np.array([np.inf, -np.inf], dtype=np.float64))
        r[0] = min(r[0], float(flat.min()))
        r[1] = max(r[1], float(flat.max()))

    def observe(self, site: str, layer: int | None, x,
                domain: tuple[float, float] | None = None) -> None:
        """Stream one site's pre-activation tensor into its histogram."""
        key = site_key(site, layer)
        # Register the key eagerly so the site inventory is complete even
        # before deferred callbacks flush.
        self.hists.setdefault(key, np.zeros(1 << self.w_in, dtype=np.int64))
        if domain is not None:
            self.domains[key] = (float(domain[0]), float(domain[1]))
        if isinstance(x, jax.core.Tracer):
            jax.debug.callback(lambda v, _k=key: self._accum(_k, v), x)
        else:
            self._accum(key, np.asarray(x))

    def observe_output(self, site: str, layer: int | None, y) -> None:
        """Stream one site's post-activation tensor into its range tracker."""
        key = site_key(site, layer)
        self.ranges.setdefault(
            key, np.array([np.inf, -np.inf], dtype=np.float64))
        if isinstance(y, jax.core.Tracer):
            jax.debug.callback(lambda v, _k=key: self._accum_out(_k, v), y)
        else:
            self._accum_out(key, np.asarray(y))

    def wrap(self, site: str, layer: int | None, act,
             domain: tuple[float, float] | None = None):
        """Wrap an activation callable so evaluating it records its input
        histogram and its output range.  ``domain`` pins this key's
        histogram quantizer range (registry sites with their own input
        domain); ``None`` keeps the capture-wide default."""
        def captured(x):
            self.observe(site, layer, x, domain=domain)
            y = act(x)
            self.observe_output(site, layer, y)
            return y
        return captured

    def observed_ranges(self) -> dict[str, np.ndarray]:
        """Finalized per-site output ranges (sites that saw data only)."""
        return {k: r.copy() for k, r in self.ranges.items()
                if np.isfinite(r).all() and r[1] >= r[0]}

    # -- inspection --------------------------------------------------------
    def sites(self) -> list[str]:
        return sorted(self.hists)

    def summary(self) -> str:
        per = ", ".join(
            f"{k}: {int((h > 0).sum())}/{h.size} bins"
            for k, h in sorted(self.hists.items()))
        return (f"capture[{self.n_batches} batches, "
                f"{self.n_samples} samples] {per}")


def model_batch(cfg, rng, batch_size: int, seq_len: int) -> dict:
    """One family-shaped random batch (tokens [+patches/frames]) — the
    single source of the batch-shaping convention shared by calibration
    capture, the serving launcher and the serving bench."""
    batch = {"tokens": np.asarray(
        rng.integers(1, cfg.vocab_size, (batch_size, seq_len)), np.int32)}
    if cfg.family == "vlm":
        batch["patches"] = np.asarray(
            rng.normal(size=(batch_size, cfg.n_patches, cfg.d_model)),
            np.float32)
    if cfg.family == "encdec":
        batch["frames"] = np.asarray(
            rng.normal(size=(batch_size, cfg.n_frames, cfg.d_model)),
            np.float32)
    return batch


def synthetic_batches(cfg, steps: int, batch_size: int = 2,
                      seq_len: int = 16, seed: int = 0) -> list[dict]:
    """Random-token calibration batches (:func:`model_batch` per step)."""
    rng = np.random.default_rng(seed)
    return [model_batch(cfg, rng, batch_size, seq_len)
            for _ in range(steps)]


def capture_model(params, cfg, batches, *, w_in: int | None = None,
                  x_lo: float = -8.0, x_hi: float = 8.0,
                  capture: ActivationCapture | None = None,
                  ) -> ActivationCapture:
    """Stream calibration batches through the exact forward, capturing
    every activation site's observed input bins.

    Runs the plain (non-LUT) forward of ``cfg``'s family once per batch
    with the capture context active; the layer stacks unroll so every
    family's sites are captured per layer (``L{i}/{site}`` keys) —
    encdec's decoder included.  The encdec *encoder* mlp accumulates a
    layer-agnostic ``mlp`` histogram alongside, which the per-layer keys
    shadow when masks are resolved.
    """
    from repro.nn.transformer import (
        decoder_forward,
        encdec_forward,
        encoder_forward,
        hybrid_forward,
        rwkv_forward,
    )

    cap = capture or ActivationCapture(
        w_in=w_in or cfg.lut_act_bits_in, x_lo=x_lo, x_hi=x_hi)
    with cap:
        for batch in batches:
            if not isinstance(batch, dict):
                batch = {"tokens": batch}
            toks = np.asarray(batch["tokens"], np.int32)
            if cfg.mla is not None and cfg.moe is not None:
                # the latent stack's expert layers are the held,
                # dropless ones (repro.nn.moe.moe_ffn_held): captured op
                # by op, each one's tile loop would compile anew (its
                # trip count and capture keys are its own), ~200
                # compiles for DeepSeek-V2-Lite, so capture as one program
                out = jax.jit(lambda p, t: decoder_forward(p, cfg, t)[0])(
                    params, toks)
            elif cfg.family in ("dense", "moe", "vlm"):
                out, _, _ = decoder_forward(params, cfg, toks,
                                            patches=batch.get("patches"))
            elif cfg.family == "ssm":
                out, _ = rwkv_forward(params, cfg, toks)
            elif cfg.family == "hybrid":
                out, _ = hybrid_forward(params, cfg, toks)
            elif cfg.family == "encdec":
                enc = encoder_forward(params, cfg, batch["frames"])
                out, _ = encdec_forward(params, cfg, toks, enc)
            else:
                raise ValueError(f"capture_model: unknown family "
                                 f"{cfg.family!r}")
            jax.block_until_ready(out)
            # The softcap site lives past the forwards above (they return
            # hidden states, not logits): project explicitly so the
            # network-global tanh histogram is observed too.
            if sites.site_spec(sites.LOGIT_SOFTCAP).active(cfg):
                from repro.nn.mlp import project_logits

                jax.block_until_ready(
                    project_logits(out, params["lm_head"], cfg))
            cap.n_batches += 1
    # Deferred debug callbacks must land before masks are derived.
    jax.effects_barrier()
    return cap
