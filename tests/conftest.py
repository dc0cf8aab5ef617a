"""Shared test fixtures + a minimal ``hypothesis`` fallback.

The container this repo targets does not ship ``hypothesis`` and nothing
may be pip-installed, so when the real package is missing we register a
small deterministic stand-in under ``sys.modules['hypothesis']`` *before*
test modules import it.  The stub supports exactly the API surface these
tests use — ``given``/``settings`` and the ``integers``/``floats``/
``booleans``/``sampled_from`` strategies — and draws ``max_examples``
seeded pseudo-random examples per test, so property tests still exercise
a spread of inputs (reproducibly) instead of being skipped.
"""
from __future__ import annotations

import sys
import types
import zlib

import pytest


def _install_hypothesis_stub() -> None:
    try:
        import hypothesis  # noqa: F401

        return
    except ImportError:
        pass

    import numpy as np

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rng):
            return self._draw(rng)

        def map(self, fn):
            return _Strategy(lambda rng: fn(self._draw(rng)))

        def filter(self, pred, _tries=1000):
            def draw(rng):
                for _ in range(_tries):
                    v = self._draw(rng)
                    if pred(v):
                        return v
                raise ValueError("filter predicate too strict for stub")

            return _Strategy(draw)

    def integers(min_value=0, max_value=1 << 16):
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1))
        )

    def floats(min_value=0.0, max_value=1.0, **_kw):
        return _Strategy(
            lambda rng: float(rng.uniform(min_value, max_value))
        )

    def booleans():
        return _Strategy(lambda rng: bool(rng.integers(0, 2)))

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(
            lambda rng: elements[int(rng.integers(0, len(elements)))]
        )

    def just(value):
        return _Strategy(lambda rng: value)

    def settings(max_examples=10, deadline=None, **_kw):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn

        return deco

    def given(*_args, **strategies):
        if _args:
            raise TypeError("hypothesis stub supports keyword strategies only")

        def deco(fn):
            def wrapper():
                n = getattr(
                    wrapper,
                    "_stub_max_examples",
                    getattr(fn, "_stub_max_examples", 10),
                )
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = np.random.default_rng(seed)
                for _ in range(n):
                    fn(**{k: s.draw(rng) for k, s in strategies.items()})

            wrapper.__name__ = fn.__name__
            wrapper.__qualname__ = fn.__qualname__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            if hasattr(fn, "_stub_max_examples"):
                wrapper._stub_max_examples = fn._stub_max_examples
            return wrapper

        return deco

    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.integers = integers
    st_mod.floats = floats
    st_mod.booleans = booleans
    st_mod.sampled_from = sampled_from
    st_mod.just = just

    hyp_mod = types.ModuleType("hypothesis")
    hyp_mod.given = given
    hyp_mod.settings = settings
    hyp_mod.assume = lambda cond: True
    hyp_mod.strategies = st_mod
    hyp_mod.__stub__ = True

    sys.modules["hypothesis"] = hyp_mod
    sys.modules["hypothesis.strategies"] = st_mod


_install_hypothesis_stub()


@pytest.fixture(scope="module", autouse=True)
def _no_kept_programs():
    """``repro.serve.generate`` keeps compiled programs process-wide:
    each test module starts without them, so its first call of a shape
    builds its programs whatever ran before in the same process."""
    generate = sys.modules.get("repro.serve.generate")
    if generate is not None:
        generate.clear_programs()
