"""Telemetry context: one object binding registry + event log + monitor.

The activation idiom is :mod:`repro.calib.capture`'s module-level stack:
instrumented code (batcher, reloader, ladder, engine, kernel wrappers)
asks :func:`current` for the innermost active :class:`Telemetry` and
does nothing when there is none — off-by-default telemetry costs one
``None`` check on host code paths and adds **zero traced ops** to jitted
steps (the don't-care monitor's callbacks only exist while its context
is entered, asserted in tests/test_obs.py).

:func:`span` is the one exception to "does nothing": with or without a
:class:`Telemetry`, it enters a ``jax.profiler.TraceAnnotation`` named
``repro:<name>`` (its fields ride along as annotation metadata), so the
program's spans land in a profiler trace on the device trace's clock.
With no profiler running, that annotation is one cheap host call and is
recorded nowhere.  "Off" therefore means: no ``EventLog`` records, no
traced ops, and ``repro:`` annotations that only a profiler records.

Entering a :class:`Telemetry` also enters its
:class:`~repro.obs.drift.DontCareMonitor` (when attached); exiting
flushes deferred callbacks, emits one ``drift`` event per observed site
key, writes the metrics snapshot into the event log's ``obs_end``
footer, and optionally dumps the Prometheus text exposition to
``prom_path`` (atomic tmp + replace, the ioutil write discipline).
"""
from __future__ import annotations

import os
from contextlib import contextmanager, nullcontext

from jax.profiler import TraceAnnotation

from .drift import DontCareMonitor
from .events import EventLog
from .metrics import MetricsRegistry

_STACK: list["Telemetry"] = []


def telemetry_active() -> bool:
    return bool(_STACK)


def current() -> "Telemetry | None":
    return _STACK[-1] if _STACK else None


class Telemetry:
    """Registry + event log + (optional) don't-care monitor, as one
    context.  All pieces are optional; a bare ``Telemetry()`` records
    metrics in memory only."""

    def __init__(self, *, registry: MetricsRegistry | None = None,
                 events: EventLog | None = None,
                 monitor: DontCareMonitor | None = None,
                 prom_path: str | None = None):
        self.registry = registry or MetricsRegistry()
        self.events = events
        self.monitor = monitor
        self.prom_path = prom_path
        self._entered = False
        self._monitor_entered = False
        self._finished = False

    # -- context management --------------------------------------------------
    def __enter__(self) -> "Telemetry":
        _STACK.append(self)
        self._entered = True
        if self.monitor is not None and not self._monitor_entered:
            self.monitor.__enter__()
            self._monitor_entered = True
        return self

    def __exit__(self, *exc) -> None:
        _STACK.remove(self)
        self._entered = False
        self.finish()

    def attach_monitor(self, monitor: DontCareMonitor) -> None:
        """Late-bind a drift monitor (the launcher learns its calibration
        after telemetry starts); activates it if we are already entered."""
        self.monitor = monitor
        if self._entered and not self._monitor_entered:
            monitor.__enter__()
            self._monitor_entered = True

    def finish(self) -> None:
        """Flush + export: drift events, metrics footer, Prometheus dump.
        Idempotent; runs automatically on context exit."""
        if self._finished:
            return
        self._finished = True
        if self._monitor_entered:
            self.monitor.__exit__(None, None, None)
            self._monitor_entered = False
        if self.monitor is not None:
            for key, row in self.monitor.drift().items():
                self.event("drift", site=key, **row)
                self.registry.gauge(
                    "lut_dontcare_served_frac",
                    "served lookup fraction landing in don't-care bins",
                ).set(row["served_dontcare_frac"], site=key)
        if self.events is not None:
            self.events.close(metrics=self.registry.snapshot())
        if self.prom_path is not None:
            tmp = self.prom_path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                fh.write(self.registry.render_prometheus())
            os.replace(tmp, self.prom_path)

    # -- convenience ---------------------------------------------------------
    def event(self, name: str, *, sampled: bool = False, **fields) -> None:
        if self.events is not None:
            self.events.emit(name, sampled=sampled, **fields)

    def span(self, name: str, **fields):
        if self.events is not None:
            return self.events.span(name, **fields)
        return nullcontext()


# -- module-level no-op-when-inactive helpers --------------------------------
def event(name: str, *, sampled: bool = False, **fields) -> None:
    t = current()
    if t is not None:
        t.event(name, sampled=sampled, **fields)


@contextmanager
def span(name: str, **fields):
    """A ``repro:<name>`` profiler annotation, always; plus the active
    telemetry's ``span_begin``/``span_end`` records when there is one.
    Trace readers match on the name alone."""
    with TraceAnnotation(f"repro:{name}", **fields):
        t = current()
        with t.span(name, **fields) if t else nullcontext() as sid:
            yield sid


def count(name: str, amount: float = 1.0, help: str = "", **labels) -> None:
    t = current()
    if t is not None:
        t.registry.counter(name, help).inc(amount, **labels)


def gauge(name: str, value: float, help: str = "", **labels) -> None:
    t = current()
    if t is not None:
        t.registry.gauge(name, help).set(value, **labels)


def observe(name: str, value: float, help: str = "", **labels) -> None:
    t = current()
    if t is not None:
        t.registry.histogram(name, help).observe(value, **labels)
