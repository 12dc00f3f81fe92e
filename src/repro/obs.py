"""Spans and per-fit counters: the one place the program traces from.

A span (``with obs.span("lloyd.pull"):``) is a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``.  While a profiler
trace is taken it is a host event on the profiler's clock, the clock of the
device's operations, nested under the span open around it on the same
thread.  Otherwise it costs the annotation and two clock reads, about a
microsecond.  Spans opened inside a fit carry its id as the arg ``fit``.

A fit (``with obs.fit() as rec:``) is a record open on the current thread.
It counts what happens while it is open: XLA compilations and their
seconds, persistent-cache hits (the ``jax.monitoring`` events the JAX
compiler records), and the host seconds of every span opened in it, by
name.  The Lloyd and mesh drivers write each history row's share of these
(:class:`RowCounts`).  Nothing is switched on: the profiler is the only switch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time

import jax
import jax.monitoring

PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_local = threading.local()
_fit_ids = itertools.count(1)


@dataclasses.dataclass
class FitRecord:
    """What happened on one thread while a fit was open."""
    id: int
    compiles: int = 0
    compile_s: float = 0.0
    cache_hits: int = 0
    span_s: dict = dataclasses.field(default_factory=dict)


def open_fit() -> FitRecord | None:
    """The fit open on this thread, or None."""
    return getattr(_local, "fit", None)


@contextlib.contextmanager
def fit():
    """Open a fit record on this thread, or join the one already open (a
    fit inside a fit, such as the per-cell fits of a two-level fit, counts
    into the outer one).  Yields the record."""
    rec = open_fit()
    if rec is not None:
        yield rec
        return
    rec = _local.fit = FitRecord(id=next(_fit_ids))
    try:
        yield rec
    finally:
        _local.fit = None


@contextlib.contextmanager
def span(name: str, **args):
    """A ``repro.<name>`` host span with ``args`` (and the open fit's id)."""
    rec = open_fit()
    if rec is not None:
        args = {"fit": rec.id, **args}
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name, **args):
            yield
    finally:
        if rec is not None:
            rec.span_s[name] = (rec.span_s.get(name, 0.0)
                                + time.perf_counter() - t0)


class RowCounts:
    """Each history row's share of the open fit's counters: what happened
    since the previous row was taken, so that a fit's rows sum to what
    happened inside it.  ``compiles``, ``compile_s`` and ``cache_hits`` are
    XLA compilations, their seconds and persistent-cache hits;
    ``estparams_s`` is the host time of the ``repro.estparams`` span."""

    def __init__(self, rec: FitRecord):
        self._rec = rec
        self._mark = self._now()

    def _now(self) -> dict:
        rec = self._rec
        return {"compiles": rec.compiles, "compile_s": rec.compile_s,
                "cache_hits": rec.cache_hits,
                "estparams_s": rec.span_s.get("estparams", 0.0)}

    def take(self) -> dict:
        now = self._now()
        share = {key: now[key] - self._mark[key] for key in now}
        self._mark = now
        return share


def _on_duration(event: str, duration: float, **_) -> None:
    rec = open_fit()
    if rec is not None and event == COMPILE_EVENT:
        rec.compiles += 1
        rec.compile_s += duration


def _on_event(event: str, **_) -> None:
    rec = open_fit()
    if rec is not None and event == CACHE_HIT_EVENT:
        rec.cache_hits += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
