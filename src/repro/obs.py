"""Spans and per-fit counters for the BWKM host driver.

A span is a ``jax.profiler.TraceAnnotation``: with no profiler running it
costs about a microsecond and records nothing; under any ``jax.profiler``
trace it lands on the host planes, on the same clock as the device's ops,
so a trace shows what the host loop was doing in each device idle gap.
Every span opened inside a fit carries the fit's sequence number as
``fit=``; the span names are listed in PERF.md §3.

The counters of one fit live in a ``contextvars.ContextVar``, so fits on
different threads never mix:

  * ``host_syncs``  — device-to-host reads made for the fit (:func:`pull`);
  * ``data_passes`` — operations that read every row of the data
    (:func:`data_pass`).

:func:`fit_scope` opens the counters of a fit, or joins the scope already
open, so nested entry points (the estimator around the driver) count once.
Outside any scope :func:`pull` still converts and :func:`data_pass` does
nothing. Like ``repro.health``, this module imports nothing of ``repro``.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
from typing import Any, Callable, Iterator

import jax

__all__ = ["FitCounters", "data_pass", "fit_scope", "pull", "span"]


class FitCounters:
    """One fit's sequence number (``fit=`` on its spans) and its counters."""

    def __init__(self, seq: int):
        self.seq = seq
        self.counts = {"host_syncs": 0, "data_passes": 0}


_CURRENT: contextvars.ContextVar[FitCounters | None] = contextvars.ContextVar(
    "repro_obs_fit", default=None
)
_SEQ = itertools.count(1)


def span(name: str, **ids: Any) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``; inside a fit it also carries ``fit=``."""
    fc = _CURRENT.get()
    if fc is not None:
        ids = {"fit": fc.seq, **ids}
    return jax.profiler.TraceAnnotation(name, **ids)


@contextlib.contextmanager
def fit_scope() -> Iterator[FitCounters]:
    """Open a fit's counters and its ``bwkm.fit`` span, or join the open one."""
    fc = _CURRENT.get()
    if fc is not None:
        yield fc
        return
    fc = FitCounters(next(_SEQ))
    token = _CURRENT.set(fc)
    try:
        with span("bwkm.fit"):
            yield fc
    finally:
        _CURRENT.reset(token)


def pull(x: Any, convert: Callable[[Any], Any] = float) -> Any:
    """``convert(x)`` — ``float``, ``int``, ``bool`` or ``jax.device_get`` —
    counted as one host sync of the open fit when ``x`` is a device array."""
    value = convert(x)
    fc = _CURRENT.get()
    if fc is not None and isinstance(x, jax.Array):
        fc.counts["host_syncs"] += 1
    return value


def data_pass() -> None:
    """Count one read of every row of the fit's data."""
    fc = _CURRENT.get()
    if fc is not None:
        fc.counts["data_passes"] += 1
