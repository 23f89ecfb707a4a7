"""Spans of the program's own phases, on the profiler's clock.

``span(name, **attrs)`` marks a phase::

    with tracing.span("repro.solve.h2d", bytes=n) as sp:
        v = jax.device_put(v, chip)
        sp.fence(v)

While a profiler trace is active (``jax.profiler.start_trace`` ...
``stop_trace``) a span opens a ``jax.profiler.TraceAnnotation`` of the
same name and attrs, which puts the phase on the trace's ``/host:CPU``
plane on the device ops' clock, and keeps a record of it in memory:
name, start and end (``time.perf_counter_ns``), the enclosing span's
name, a solve id and the attrs.  ``fence(arrays)`` names the arrays the
phase produced; a recording span waits for them at its exit, so it
covers the work and not only its dispatch.

A span with no enclosing span is a root (one solve).  Whether to record
is decided once, at the root's start, from the profiler's own flag
(:func:`repro.compat.profiler_active`); every span inside it follows, so
a solve is recorded whole or not at all.  With no trace active a span
reads its clock and nothing else: nothing is annotated, fenced or
stored.  :func:`snapshot` returns the records of the newest profiler
session (a session begins at the first root recorded after one that was
not).
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

import jax

from repro import compat

__all__ = ["Span", "span", "snapshot", "reset"]

_lock = threading.Lock()
_local = threading.local()
_records: List[dict] = []
_was_active = False
_solves = 0


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _begin_root() -> Optional[int]:
    """Solve id of a new root span when a trace is active, else None."""
    global _records, _was_active, _solves
    active = compat.profiler_active()
    with _lock:
        if active and not _was_active:
            _records = []
        _was_active = active
        if not active:
            return None
        _solves += 1
        return _solves


class Span:
    """One phase; use through :func:`span`.  ``seconds`` is its length,
    recorded or not."""

    __slots__ = ("name", "attrs", "solve", "parent", "start_ns", "end_ns",
                 "_fenced", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.solve = self.parent = None
        self.start_ns = self.end_ns = None
        self._fenced = self._annotation = None

    @property
    def recording(self) -> bool:
        return self.solve is not None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def fence(self, *arrays) -> None:
        """Wait for ``arrays`` at exit, when this span records."""
        if self.recording:
            self._fenced = arrays

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            self.solve, self.parent = stack[-1].solve, stack[-1].name
        else:
            self.solve = _begin_root()
        if self.recording:
            self._annotation = jax.profiler.TraceAnnotation(
                self.name, **self.attrs)
            self._annotation.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if self._fenced is not None and exc_type is None:
                jax.block_until_ready(self._fenced)
        finally:
            self.end_ns = time.perf_counter_ns()
            self._fenced = None
            _stack().pop()
            if self.recording:
                self._annotation.__exit__(exc_type, exc, tb)
                record = {"name": self.name, "solve": self.solve,
                          "parent": self.parent,
                          "start_ns": self.start_ns,
                          "end_ns": self.end_ns, "attrs": self.attrs}
                with _lock:
                    _records.append(record)


def span(name: str, **attrs) -> Span:
    """A context manager marking the phase ``name``; ``attrs`` (counts
    such as ``bytes`` or ``columns``) go on its annotation and record."""
    return Span(name, attrs)


def snapshot() -> List[dict]:
    """The records of the newest profiler session, in the order the
    spans ended: dicts with ``name``, ``solve``, ``parent``,
    ``start_ns``, ``end_ns`` and ``attrs``."""
    with _lock:
        return list(_records)


def reset() -> None:
    """Forget every record and the session state."""
    global _records, _was_active, _solves
    with _lock:
        _records, _was_active, _solves = [], False, 0
