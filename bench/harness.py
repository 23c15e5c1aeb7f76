"""One run of one cell: set-up, the measured window, the correctness check,
the metrics, and the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run needs a TPU (or as many as the cell asks for) and exits 1 without
printing a result when JAX finds another platform.  It keeps JAX's
persistent compilation cache at ``<checkout>/.bench_cache/jax``, writes its
traces under ``$TMPDIR`` and removes them, and prints as its last line of
standard output one JSON object; the numbers the correctness check compared
come last in it, under ``checks``, and again as the last lines of standard
error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from typing import List, Optional

import numpy as np

import check
import devtrace
import drive
import traffic
from spec import BENCH_DIR, Cell, read_metrics, root_of

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Backend compiles while armed (JAX monitoring events)."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also compare the float32 reference in the "
                         "program's place (the control; not in timed runs)")
    return ap.parse_args(argv)


def device_facts(chips: int, require_tpu: bool):
    """(facts, error): the device as JAX reports it."""
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if require_tpu and facts["platform"] != "tpu":
        return facts, (f"needs a TPU; JAX's first device is "
                       f"{facts['platform']!r} ({facts['kind']!r})")
    if require_tpu and facts["count"] < chips:
        return facts, (f"the cell needs {chips} chip(s); JAX found "
                       f"{facts['count']}")
    return facts, None


def enable_cache(path: str) -> None:
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _compiled_names(before: dict) -> List[str]:
    from repro.analysis.retrace import compile_counts

    after = compile_counts()
    return sorted(n for n, c in after.items() if c != before.get(n, 0))


def _checked(dep, kept, search: bool, control: bool):
    """Program (and control) readings over the sampled explorations, and
    the first packets whose occupancy differs."""
    prog_readings, ctrl_readings, diffs = [], [], []
    for idx, seed, rows, trace, report in kept:
        prog = check.program_answers(*rows, report)
        want = check.reference_answers(dep, trace, prog, search)
        prog_readings.append(check.compare(prog, want))
        diffs += [(idx, seed) + d for d in check.occupancy_diffs(prog, want)]
        if control:
            low = check.reference_answers(dep, trace, prog, search,
                                          np.float32)
            ctrl_readings.append(check.compare(low, want))
    return (check.worst(prog_readings),
            check.worst(ctrl_readings) if control else None, diffs)


def run_cell(args, cell: Cell, t_start: float, facts: dict, peaks: dict,
             tmp: str) -> dict:
    mix, config = cell.mix, cell.config
    search = mix.get("search") is not None
    dep = check.deployment(config)
    warm = int(mix["warm"])
    pool = traffic.TracePool(config, mix, args.seed, tmp,
                             traffic.pool_size(mix, args.seconds))
    pool.fill()
    search_seeds = traffic.search_seeds(args.seed, len(pool.seeds))

    program = drive.Program(config, mix)
    departures = program.departures(config, pool.get(0))
    if departures:
        raise SystemExit("the program departs from the configuration: "
                         + "; ".join(departures))
    probe = drive.Probe()
    probe.install()
    counter = CompileCounter()

    from repro.analysis.retrace import compile_counts

    drive.closed_warmup(program, pool, probe, warm, search_seeds)
    if mix.get("warm_rows"):
        drive.warm_rows(program, pool.get(0), probe, mix["warm_rows"])
    jit_before = compile_counts()
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(tmp, "profile")
    tracer = devtrace.Tracer(trace_dir, float(mix["trace_seconds"]),
                             bool(args.trace), probe.snapshot)
    tracer.begin()
    counter.armed = True
    out = drive.closed_window(program, pool, probe, first=warm,
                              seconds=args.seconds, sample=int(mix["check"]),
                              seed=args.seed, search_seeds=search_seeds,
                              tracer=tracer)
    counter.armed = False
    tracer.end()
    facts = dict(facts, memory_peak_bytes=_peak_bytes())

    ctx = {"search": search, "setup_s": setup_s,
           "window_s": out["window_s"], "seconds": args.seconds,
           "explorations": out["explorations"],
           "compiles": counter.count,
           "compiled": _compiled_names(jit_before),
           "traced_work": tracer.counts,
           "traced_until": (tracer.stopped_at - out["t0"]
                            if tracer.stopped_at is not None else None),
           "peaks": peaks, "events": None, "win": None}
    breakdown = None
    if args.trace:
        events = devtrace.load(trace_dir)
        win = devtrace.window(events)
        ctx.update(events=events, win=win)
        if win is not None:
            busy = devtrace.busy_seconds(events, win)
            ctx["programs"] = devtrace.program_seconds(events, win)
            facts.update(busy_s=busy if busy is not None else 0.0,
                         window_s=(win[1] - win[0]) * 1e-9)
            breakdown = {"device_ops": devtrace.top_programs(events, win),
                         "idle_gaps": devtrace.idle_gaps(events, win)}

    # the correctness check runs after the window, on the sampled items
    kept = [(i, pool.seeds[i], rows, pool.trace(i), rep.to_dict())
            for i, rep, rows in out["kept"]]
    t_check = time.perf_counter()
    numbers, control, diffs = _checked(dep, kept, search, bool(args.control))
    check_s = time.perf_counter() - t_check
    failed = len(out["failures"])
    correct = bool(kept) and failed == 0 and check.judge(numbers, cell.limits)

    metrics = read_metrics(
        cell, cell.per_layer() if args.trace else cell.end_to_end(), ctx)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": failed, "metrics": metrics, "device": facts}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result.update({
        "workload": cell.name, "seed": args.seed,
        "checked": len(kept), "check_s": check_s,
        "compiled_in_window": ctx["compiled"],
    })
    for f in out["failures"][:5]:
        _say(f"failure: {f}")
    for idx, seed, row, k, got, want in diffs[:5]:
        _say(f"occupancy: exploration {idx} (trace seed {seed}) row {row} "
             f"packet {k}: program {got}, reference {want}")
    if control is not None:
        result["control"] = control
        for k in check.NUMBERS:
            _say(f"control {k}={control[k]!r}")
    result["checks"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                        for k in check.NUMBERS}
    return result


def main(argv: Optional[List[str]] = None, *, t_start: Optional[float] = None,
         require_tpu: bool = True, bench_dir: str = BENCH_DIR,
         root: Optional[str] = None, cache_dir: Optional[str] = None,
         src: Optional[str] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    root = root if root is not None else root_of(bench_dir)
    try:
        cell = Cell(args.workload, root, bench_dir)
    except (KeyError, OSError, ValueError) as e:
        _say(f"bench: {e}")
        return 1
    facts, error = device_facts(cell.chips, require_tpu)
    _say(f"device: {facts}")
    if error is not None:
        _say(f"bench: {error}")
        return 1
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    peaks = table.get(facts["kind"])
    if peaks is None and require_tpu:
        _say(f"bench: no peaks for device kind {facts['kind']!r} in "
             f"peaks.json (known: {sorted(table)})")
        return 1
    enable_cache(cache_dir or os.path.join(root, ".bench_cache", "jax"))
    src = src if src is not None else os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _say(f"bench: the program is not at {src}")
        return 1
    sys.path.insert(0, src)
    try:
        with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
            result = run_cell(args, cell, t_start, facts, peaks or {}, tmp)
    except SystemExit as e:
        _say(f"bench: {e}")
        return 1
    except Exception:  # noqa: BLE001 - any fault of the run is reported
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    checks = result["checks"]
    for k, v in checks.items():
        _say(f"{k}: {v['value']!r} (limit {v['limit']!r})")
    return 0
