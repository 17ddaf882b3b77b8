"""Program spans and compile counts, on the profiler's clock.

Off by default, and then nearly free: :func:`span` returns one shared
``contextlib.nullcontext()`` (no clock read, nothing recorded) and
:func:`count` returns at once. :func:`enable` turns recording on for the
whole process; :func:`collect` returns what was recorded since the last
call and forgets it::

    from repro import tracing

    tracing.enable()
    ...                               # serve, characterize
    spans, counts = tracing.collect()
    tracing.disable()

A span is ``(name, thread, start_ns, end_ns, parent, attrs)``: its name,
the native id of the thread that opened it, its ends on
``time.perf_counter_ns``, the name of the span open around it on the same
thread (None at the top) and its attributes. While it is open it is also a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so that
under ``jax.profiler.start_trace`` it lies on the profiler's host plane, on
the clock of the device's events.

A count is ``(name, thread, t_ns, n)``. The compile counter is three counts
fed by ``jax.monitoring`` listeners, registered at the first
:func:`enable`: ``compile.trace`` (a function traced to a jaxpr),
``compile.backend`` (an XLA compile, or a persistent compile-cache read in
its place) and ``compile.cache_load`` (a persistent compile-cache hit).

Span names start with ``repro.``; docs/performance.md lists them.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, NamedTuple

import jax


class Span(NamedTuple):
    name: str
    thread: int
    start_ns: int
    end_ns: int
    parent: str | None
    attrs: dict[str, Any]


class Count(NamedTuple):
    name: str
    thread: int
    t_ns: int
    n: int


_NULL = contextlib.nullcontext()
_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_COMPILE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile.cache_load"}

# process-wide, as the profiler is: one switch, two append-only lists
_on = False
_listening = False
_spans: list[Span] = []
_counts: list[Count] = []
_local = threading.local()


def enable() -> None:
    """Record spans and counts from now on, in every thread."""
    global _on, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`collect`."""
    global _on
    _on = False


def collect() -> tuple[list[Span], list[Count]]:
    """The spans and counts recorded since the last call, oldest first;
    they are forgotten here."""
    spans, counts = _spans[:], _counts[:]
    del _spans[:len(spans)], _counts[:len(counts)]
    return spans, counts


def span(name: str, *, start_ns: int | None = None, **attrs):
    """A context manager that records ``name`` over its body when tracing
    is on. ``start_ns`` reuses a ``time.perf_counter_ns()`` stamp the caller
    has just taken as the span's start."""
    if not _on:
        return _NULL
    return _Open(name, start_ns, attrs)


def count(name: str, n: int = 1) -> None:
    """Record ``n`` of ``name`` now, when tracing is on."""
    if _on:
        _counts.append(Count(name, threading.get_native_id(),
                             time.perf_counter_ns(), n))


class _Open:
    __slots__ = ("name", "start_ns", "attrs", "parent", "note")

    def __init__(self, name: str, start_ns: int | None, attrs: dict):
        self.name, self.start_ns, self.attrs = name, start_ns, attrs

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.note = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.note.__enter__()
        if self.start_ns is None:
            self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.perf_counter_ns()
        self.note.__exit__(*exc)
        _local.stack.pop()
        _spans.append(Span(self.name, threading.get_native_id(),
                           self.start_ns, end_ns, self.parent, self.attrs))


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    name = _COMPILE_DURATIONS.get(event)
    if name is not None:
        count(name)


def _on_event(event: str, **kwargs) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is not None:
        count(name)
