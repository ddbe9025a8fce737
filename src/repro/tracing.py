"""Spans inside the program, on while a JAX profiler session runs.

``span(name, **attrs)`` marks one piece of the served path (a decode round,
one hop of it, a prefill round, ...).  It is on exactly while a profiler
session is active (``jax.profiler.start_trace`` or a capture through the
profiler server): there is no other switch.  Off, it returns one shared
no-op object whose ``set`` does nothing and which is falsy, so counts are
computed only when someone is listening::

    with tracing.span("round.hop", server=j) as sp:
        if sp:
            sp.set(row_layers_useful=...)
        ...

On, each span is kept as a :class:`Span` with ``time.perf_counter()``
start and end, the id of the span open around it in the same thread
(``parent``) and its attributes, and it also enters a
``jax.profiler.TraceAnnotation`` so it shows in the profiler's host plane.
The kept spans form a ring of the last :data:`MAX_SPANS` (older ones are
dropped); :func:`spans` reads it and :func:`clear` empties it.  The ring
is process-wide, like the profiler session that turns it on.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

import jax

# Spans kept at most: a decode round of four hops records eight, so this
# holds about 8000 rounds (several minutes of serving), a few tens of MB.
MAX_SPANS = 1 << 16

_on = jax.profiler.TraceAnnotation.is_enabled


class Span(NamedTuple):
    id: int
    parent: Optional[int]  # the span open around this one, if any
    name: str
    start: float  # time.perf_counter() seconds
    end: float
    attrs: Dict[str, Any]


_buf: deque = deque(maxlen=MAX_SPANS)
_ids = itertools.count()
_open = threading.local()  # .stack: ids of this thread's open spans


class _Off:
    """The span handed out while no profiler session runs."""

    __slots__ = ()

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_OFF = _Off()


class _Live:
    __slots__ = ("id", "parent", "name", "attrs", "start", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        _open.stack.pop()
        _buf.append(Span(self.id, self.parent, self.name, self.start, end,
                         self.attrs))
        return False


def span(name: str, **attrs):
    """A context manager marking ``name``; falsy and free while no profiler
    session is active."""
    if not _on():
        return _OFF
    return _Live(name, attrs)


def spans() -> List[Span]:
    """The kept spans, in the order they ended."""
    return list(_buf)


def clear() -> None:
    _buf.clear()
