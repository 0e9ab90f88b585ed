"""Spans and counters of the decision path, on JAX's profiler clock.

Off by default. Then ``span`` hands back one shared no-op context
manager and ``count`` returns at once, so an instrumented site costs a
flag read. ``enable()`` turns both on: each span becomes a
``jax.profiler.TraceAnnotation``, recorded by whatever ``jax.profiler``
trace is running (and by nothing when none is), so the program's spans
share one clock with the device's ops; counts add up in this process
until ``reset()``.

    from repro.core import trace

    trace.enable(); trace.reset()
    jax.profiler.start_trace(log_dir)
    scheduler.place_batch(jobs)
    jax.profiler.stop_trace()
    print(trace.counters()); trace.disable()

Spans carry no request identifiers: the program is single-threaded, so
a span's parent is the span open around it on the thread, and the
counters give the per-call ratios. Hot per-job loops accumulate in
local ints and call ``count`` once per call.
"""
from __future__ import annotations

import contextlib

__all__ = ["count", "counters", "disable", "enable", "on", "reset", "span"]

on = False
_NULL = contextlib.nullcontext()
_annotation = None
_counts: dict[str, int] = {}


def enable() -> None:
    """Record spans on the profiler and accumulate counters."""
    global on, _annotation
    from jax import profiler

    _annotation = profiler.TraceAnnotation
    on = True


def disable() -> None:
    global on
    on = False


def span(name: str):
    """A context manager that marks ``name`` on the profiler's host
    timeline while tracing is on; the shared no-op otherwise."""
    return _annotation(name) if on else _NULL


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while tracing is on."""
    if on:
        _counts[name] = _counts.get(name, 0) + n


def counters() -> dict[str, int]:
    """A snapshot of the counters."""
    return dict(_counts)


def reset() -> None:
    _counts.clear()
