"""Compile-count guard for the jitted DSE engines.

The mesh-sharding contract (PR 6, ``docs/architecture.md``) includes a cost
clause the determinism tests cannot see: the lru-cached sharded engine
builders must compile **once per (shape, mesh)** — a silently retracing
engine still produces bit-identical numbers while quietly throwing away the
batched stages' entire speedup.  This module makes that clause assertable.

The engine modules register their jitted callables at creation time
(``track``); ``compile_counts`` reads each callable's jit cache size (the
number of distinct (shape, static-args) entries traced so far), and
``retrace_guard`` turns a before/after delta into a hard assertion:

    with retrace_guard(expect=1) as g:
        run_surrogate_batched(cands, bound, trace)   # first call: one trace
    with retrace_guard(expect=0):
        run_surrogate_batched(cands, bound, trace)   # same shapes: cached

Deliberately dependency-free (no jax import): the engine modules import
``track`` at module load, and this module must never create an import cycle
back through ``repro.sim``/``repro.api``.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

__all__ = ["track", "tracked_names", "compile_counts", "RetraceError",
           "RetraceGuard", "retrace_guard"]

#: name -> jitted callable; names are stable ("surrogate.engine") or derived
#: from the sharded builder's cache key ("netsim.sharded[cand=2,...]")
_TRACKED: Dict[str, Callable] = {}


def track(name: str, fn: Callable) -> Callable:
    """Register a jitted callable under a stable name and return it
    unchanged — the engine modules wrap their ``jax.jit(...)`` calls in this
    at creation time (module level and inside the lru-cached builders)."""
    _TRACKED[name] = fn
    return fn


def tracked_names():
    return sorted(_TRACKED)


def _jit_cache_size(fn: Any) -> int:
    """Distinct traced entries of one jitted callable.

    Raises ``TypeError`` for a callable without a readable jit cache: a
    silent 0 would let every zero-compilations guard pass unseen."""
    probe = getattr(fn, "_cache_size", None)
    if not callable(probe):
        raise TypeError(f"{fn!r} exposes no jit cache size; track() only "
                        "jax.jit-wrapped callables")
    return int(probe())


def compile_counts() -> Dict[str, int]:
    """Current per-engine trace counts, summable into a before/after delta."""
    return {name: _jit_cache_size(fn) for name, fn in _TRACKED.items()}


class RetraceError(AssertionError):
    """An engine traced more (or less) than the contract allows."""


class RetraceGuard:
    """Before/after snapshot of every tracked engine's jit cache."""

    def __init__(self):
        self._before = compile_counts()
        self._after: Optional[Dict[str, int]] = None

    def finish(self) -> None:
        self._after = compile_counts()

    def deltas(self) -> Dict[str, int]:
        """Per-engine new compiles since the guard opened (engines first
        tracked inside the guarded region count in full)."""
        after = self._after if self._after is not None else compile_counts()
        return {name: n - self._before.get(name, 0)
                for name, n in after.items()
                if n - self._before.get(name, 0) != 0}

    @property
    def new_compiles(self) -> int:
        return sum(self.deltas().values())


@contextlib.contextmanager
def retrace_guard(expect: Optional[int] = None):
    """Assert the guarded region compiled exactly ``expect`` new engine
    traces (``None`` = just observe; read ``.deltas()`` afterwards)."""
    guard = RetraceGuard()
    try:
        yield guard
    finally:
        guard.finish()
    if expect is not None and guard.new_compiles != expect:
        raise RetraceError(
            f"expected exactly {expect} new engine compile(s), got "
            f"{guard.new_compiles}: {guard.deltas() or '{}'}")
