"""The one traffic generator: a mix file's parameters plus ``--seed`` in,
trace files out.

A mix names how many events each trace holds, how many explorations the
warm-up runs (``warm``) and the fastest rate the window is sized for
(``max_rate_per_s``): set-up writes ``warm + ceil(seconds x max_rate_per_s)``
traces, and a window that would need more ends as a failed run rather than
make traces inside its timing.  The configuration names the trace generator
and its parameters.  Every trace seed of a run is distinct, the warm-up's
included, and all of them derive from ``--seed`` alone.
"""

from __future__ import annotations

import math
import os
from typing import List

import numpy as np

from reference import traces as ref_traces

#: SeedSequence streams of one run
_TRACE_SEEDS, _SEARCH, KEEP = 1, 4, 5
#: distinct NSGA-II seeds a run cycles through
SEARCH_SET = 16


def stream(seed: int, purpose: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, purpose]))


def distinct_seeds(seed: int, count: int) -> List[int]:
    """``count`` distinct 32-bit trace seeds drawn from ``seed``."""
    state = np.random.SeedSequence([seed % 2**64, _TRACE_SEEDS])
    out: List[int] = []
    seen = set()
    n = count
    while len(out) < count:
        for s in state.generate_state(n + len(seen) + 8, np.uint32).tolist():
            if s not in seen:
                seen.add(s)
                out.append(int(s))
        n *= 2
    return out[:count]


class PoolExhausted(IndexError):
    """The window asked for more traces than set-up wrote."""


def pool_size(mix: dict, seconds: float) -> int:
    return int(mix["warm"]) + math.ceil(seconds * float(mix["max_rate_per_s"]))


class TracePool:
    """Fresh traces of ``mix['events']`` events, written as ``.npz`` files.

    Traces ``0 .. warm-1`` are the warm-up's, the rest the window's, in the
    order the window takes them."""

    def __init__(self, config: dict, mix: dict, seed: int, directory: str,
                 capacity: int):
        self.generator = config["trace"]["generator"]
        self.params = dict(config["trace"]["params"])
        self.events = int(mix["events"])
        self.directory = directory
        self.seeds = distinct_seeds(seed, capacity)
        self.params["duration_s"] = self._duration()
        self.paths: List[str] = []

    def _duration(self) -> float:
        """A duration expected to hold ``events`` events (5% spare), from
        one trace of the configured duration; ``make_trace`` lengthens it
        for a seed that falls short."""
        probe = ref_traces.GENERATORS[self.generator](
            seed=self.seeds[0], **self.params)
        n = max(int(probe["time_s"].size), 1)
        return float(self.params["duration_s"]) * self.events / n * 1.05

    def fill(self) -> None:
        for index in range(len(self.paths), len(self.seeds)):
            tr = ref_traces.make_trace(self.generator, self.params,
                                       self.seeds[index], self.events)
            path = os.path.join(self.directory, f"trace_{index}.npz")
            ref_traces.save_npz(tr, path)
            self.paths.append(path)

    def get(self, index: int) -> str:
        if index >= len(self.paths):
            raise PoolExhausted(
                f"trace pool exhausted at trace {index}: the window ran "
                f"faster than its mix's max_rate_per_s")
        return self.paths[index]

    def trace(self, index: int) -> dict:
        """Trace ``index`` as written, read back for the check."""
        with np.load(self.paths[index]) as z:
            return {k[len("meta_"):] if k.startswith("meta_") else k:
                    z[k][()] if k.startswith("meta_") else z[k]
                    for k in z.files}


def search_seeds(seed: int, count: int) -> List[int]:
    """NSGA-II seeds for ``count`` explorations: blocks that each hold the
    same fixed set of ``SEARCH_SET`` seeds, every block in an order drawn
    from ``seed``, so every run searches alike in another order."""
    fixed = stream(0, _SEARCH).integers(0, 2**31, SEARCH_SET)
    rng = stream(seed, _SEARCH)
    blocks = -(-count // SEARCH_SET)
    return [int(s) for _ in range(blocks)
            for s in fixed[rng.permutation(SEARCH_SET)]][:count]
