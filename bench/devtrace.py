"""The profiler trace of a ``--trace 1`` run, and its reduction to numbers.

``Tracer`` profiles the first seconds of a window (the profiler records
every operation of every scan step, so a whole 51-second window would take
minutes to read), with the Python tracer off (the explorer's host loop
would otherwise fill the trace with every Python call).  ``load`` reads
the ``.xplane.pb`` into plain event lists, operations named by their HLO
instruction (``%fusion.17``):

  {"device": {plane: {line: [[name, start_ns, dur_ns], ...]}},
   "host":   [[name, start_ns, dur_ns], ...]}      # the benchmark's spans

and the functions below reduce those lists; they take no JAX object, so a
small recorded trace (``tests/data``) checks them.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: the benchmark's own spans (``jax.profiler.TraceAnnotation`` names)
SPAN_PREFIX = "bench."
TRACED_SPAN = "bench.traced"
#: device lines: per-op events for busy time, per-program for kernel time
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(logdir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


class Tracer:
    """Profiles from ``begin()`` until the first ``poll()`` (called after
    each exploration) at which ``seconds`` have passed, inside
    one ``bench.traced`` span: the traced window.  ``count()`` (the probe's
    work counters) is read at both ends, so work and device time cover the
    same window."""

    def __init__(self, logdir: str, seconds: float, on: bool, count):
        self.logdir, self.seconds, self.on = logdir, seconds, on
        self.count = count
        self.active = False
        self.counts = None
        self.stopped_at = None

    def begin(self) -> None:
        import time

        import jax

        if not self.on:
            return
        self._first = self.count()
        start(self.logdir)
        self._span = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        self.active = True

    def poll(self) -> None:
        """Stop once ``seconds`` have passed."""
        import time

        if self.active and time.perf_counter() - self._t0 >= self.seconds:
            self.end()

    def end(self) -> None:
        if self.active:
            import time

            self.stopped_at = time.perf_counter()
            self._span.__exit__(None, None, None)
            stop()
            self.active = False
            last = self.count()
            self.counts = {k: last[k] - self._first[k] for k in last}


def _short(name: str) -> str:
    return name.split(" = ", 1)[0]


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    out: dict = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [[_short(e.name), float(e.start_ns),
                                         float(e.duration_ns)]
                                        for e in line.events]
            if lines:
                out["device"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
    return out


# ---------------------------------------------------------------- reduction

def window(events: dict) -> Optional[Interval]:
    spans = [(s, s + d) for n, s, d in events["host"] if n == TRACED_SPAN]
    return max(spans, key=lambda iv: iv[1] - iv[0]) if spans else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], win: Interval) -> List[Interval]:
    lo, hi = win
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def tpu_planes(events: dict) -> List[str]:
    return sorted(p for p in events["device"] if "TPU" in p)


def busy(events: dict, plane: str, win: Interval) -> List[Interval]:
    """Union of the intervals in which an operation ran on ``plane``."""
    lines = events["device"][plane]
    evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
    return union(clip([(s, s + d) for _, s, d in evs], win))


def busy_seconds(events: dict, win: Interval) -> Optional[float]:
    """Busy seconds averaged over the chips traced; None without a chip."""
    planes = tpu_planes(events)
    if not planes:
        return None
    total = sum(sum(b - a for a, b in busy(events, p, win)) for p in planes)
    return total / len(planes) * 1e-9


def idle_gaps(events: dict, win: Interval, top: int = 10) -> List[list]:
    """The longest gaps in which the first chip ran nothing, each named by
    the benchmark span that covers most of it (the innermost on a tie)."""
    planes = tpu_planes(events)
    if not planes:
        return []
    lo, hi = win
    gaps, cur = [], lo
    for a, b in busy(events, planes[0], win):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n not in (TRACED_SPAN, "bench.window")]
    out = []
    for a, b in gaps[:top]:
        cover = [(min(b, e) - max(a, s), -(e - s), n) for n, s, e in spans]
        best = max(cover, default=(0.0, 0.0, ""))
        name = best[2] if best[0] > 0 else "bench.outside_spans"
        out.append([name, (b - a) * 1e-9])
    return out


def program_seconds(events: dict, win: Interval) -> Dict[str, float]:
    """Device seconds per XLA program (module) on the first chip."""
    planes = tpu_planes(events)
    if not planes:
        return {}
    out: Dict[str, float] = {}
    for name, s, d in events["device"][planes[0]].get(MODULES_LINE, []):
        a, b = max(s, win[0]), min(s + d, win[1])
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def top_programs(events: dict, win: Interval, top: int = 10) -> List[list]:
    """Device seconds per XLA program on the first chip, the programs of one
    jitted function (``jit__engine_impl(<fingerprint>)``) summed, largest
    first."""
    tot: Dict[str, float] = {}
    for name, v in program_seconds(events, win).items():
        key = name.split("(", 1)[0]
        tot[key] = tot.get(key, 0.0) + v
    return [[n, v] for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def seconds_of(programs: Dict[str, float], prefixes: Sequence[str]) -> float:
    """Device seconds of the programs whose name starts with a prefix."""
    return sum(v for n, v in programs.items()
               if any(n.startswith(p) for p in prefixes))
