"""Spans and counters: what the explorer is doing, layer by layer.

One mechanism for the whole program.  ``span(name, **attrs)`` is a context
manager that always times itself (``.seconds``, from
``time.perf_counter_ns``), so callers read durations off it instead of
keeping their own clock pairs.  It *records* only while a JAX profiler
session is active (``jax.profiler.TraceAnnotation.is_enabled()``): then it
also opens a ``TraceAnnotation`` of the same name, so the span appears on
the profiler's host lines on the device trace's clock, and on exit it
appends a ``Record`` to a bounded in-memory ring (``records()``).  Nothing
is written to disk and nothing exports while the program runs.

    with span("spac.stage2", rows=len(cands)) as sp:
        out = engine(...)
        note(events=m)            # onto the innermost recording span
    took = sp.seconds

Rules:

* Each record holds its name, start and end (``perf_counter_ns``), its own
  id, its parent's id and its root's id.  A span opened with no recording
  span around it is a root (one ``run_scenario`` call, one serve tick); a
  ``detached`` span is a root that never sits on the stack (one served
  request, which lives across many ticks).
* ``note(**counts)`` attaches attributes to the innermost recording span:
  numbers add up, anything else replaces.
* A span opened directly inside an open span of the same name joins it: its
  attributes go to the outer record and it records nothing itself.  So a
  caller that times a call (``run_scenario`` around ``verify_batch``) and
  the callee that opens the layer's span share one record.
* ``jit=`` names the jitted callable a device-call span runs; on recording,
  the span notes ``compiled=1`` when that callable's jit cache grew, so a
  recompile is named by the step that paid for it.

Cost with recording off: one ``is_enabled()`` check and two clock reads per
span, so no span belongs inside a per-row or per-event loop.

Dependency-free at import (JAX resolves on the first span) and never
imports ``repro.sim`` or ``repro.api``: ``repro.core`` and the engine
modules import it.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional

from .retrace import _jit_cache_size

__all__ = ["Record", "span", "note", "records", "dropped", "clear",
           "RING_SIZE"]

#: spans kept; older ones fall off the ring and count as ``dropped()``
RING_SIZE = 65_536


class Record(NamedTuple):
    """One finished span."""

    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: Optional[int]
    root: int
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_RING: Deque[Record] = collections.deque(maxlen=RING_SIZE)
_DROPPED = [0]
_IDS = itertools.count(1)
_LOCAL = threading.local()
_TraceMe: Any = None               # jax.profiler.TraceAnnotation, on first use


def _trace_me():
    global _TraceMe
    if _TraceMe is None:
        from jax.profiler import TraceAnnotation
        _TraceMe = TraceAnnotation
    return _TraceMe


def _stack() -> List["span"]:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def _add(attrs: Dict[str, Any], counts: Dict[str, Any]) -> None:
    for k, v in counts.items():
        old = attrs.get(k)
        if isinstance(v, (int, float)) and isinstance(old, (int, float)):
            attrs[k] = old + v
        else:
            attrs[k] = v


class span:
    """A timed span; records while a profiler session is active."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "id", "parent",
                 "root", "_jit", "_jit_before", "_ann", "_host", "_detached")

    def __init__(self, name: str, *, jit: Optional[Callable] = None,
                 detached: bool = False, **attrs):
        self.name = name
        self.attrs = attrs
        self._jit = jit
        self._detached = detached
        self._host: Optional[span] = None   # the record this span writes to
        self.end_ns: Optional[int] = None

    # ------------------------------------------------------------ lifetime
    def __enter__(self) -> "span":
        if (_TraceMe or _trace_me()).is_enabled():
            self._open()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._host is not None:
            self._close()

    #: a span that outlives one block (a served request) begins and ends
    #: explicitly
    begin = __enter__

    def end(self) -> None:
        self.__exit__(None, None, None)

    @property
    def seconds(self) -> float:
        """Duration; while the span is still open, the time so far."""
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return (end - self.start_ns) * 1e-9

    def note(self, **counts) -> None:
        """Attributes on this span's record (no-op when not recording)."""
        if self._host is not None:
            _add(self._host.attrs, counts)

    # ----------------------------------------------------------- recording
    def _open(self) -> None:
        st = _stack()
        top = st[-1] if st and not self._detached else None
        if top is not None and top.name == self.name:
            self._host = top._host              # join the caller's span
            _add(top._host.attrs, self.attrs)
            st.append(self)
            return
        self._host = self
        self.id = next(_IDS)
        self.parent = top._host.id if top is not None else None
        self.root = top._host.root if top is not None else self.id
        self._jit_before = (_jit_cache_size(self._jit)
                            if self._jit is not None else None)
        self._ann = _TraceMe(self.name)
        self._ann.__enter__()
        if not self._detached:
            st.append(self)

    def _close(self) -> None:
        if not self._detached:
            st = _stack()
            if st and st[-1] is self:
                st.pop()
        if self._host is not self:
            return
        self._ann.__exit__(None, None, None)
        if self._jit is not None and _jit_cache_size(self._jit) > self._jit_before:
            self.attrs["compiled"] = 1
        if len(_RING) == _RING.maxlen:
            _DROPPED[0] += 1
        _RING.append(Record(self.name, self.start_ns, self.end_ns, self.id,
                            self.parent, self.root, self.attrs))


def note(**counts) -> None:
    """Attributes on the innermost recording span: numbers add up, anything
    else replaces.  A no-op when nothing records."""
    st = getattr(_LOCAL, "stack", None)
    if st:
        _add(st[-1]._host.attrs, counts)


def records() -> List[Record]:
    """The finished spans in the ring, oldest first."""
    return list(_RING)


def dropped() -> int:
    """Spans that fell off the full ring since the last ``clear()``."""
    return _DROPPED[0]


def clear() -> None:
    _RING.clear()
    _DROPPED[0] = 0
