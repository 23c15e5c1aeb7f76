"""The system under test, reached through its public entry point, and the
loop that drives it: a closed loop of explorations, one client, each a
``repro.api.run_scenario`` call on a fresh trace.

Every call into the program sits inside one of the benchmark's own spans
(``bench.window``, ``bench.explore``), which the traced run uses to name
idle gaps.  A probe on
the switch problem's two batch hooks keeps what the timed path produced for
the correctness check and counts the rows x events each stage pushed
through the device.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from traffic import KEEP, PoolExhausted, TracePool, stream


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Probe:
    """Wraps ``SwitchDSEProblem.surrogate_batch`` / ``verify_batch``."""

    def __init__(self):
        self.rows: Dict[int, tuple] = {}
        self.work = {"stage2": 0, "stage4": 0}

    def install(self) -> None:
        from repro.sim.switch_problem import SwitchDSEProblem

        probe = self

        def wrap(orig, slot, stage):
            def hook(problem, cands):
                cands = list(cands)
                out = orig(problem, cands)
                probe.work[stage] += len(cands) * len(problem.trace)
                probe.rows.setdefault(id(problem), ([], []))[slot].extend(
                    zip(cands, out))
                return out
            hook.__wrapped__ = orig
            return hook

        def bare(fn):
            return getattr(fn, "__wrapped__", fn)

        SwitchDSEProblem.surrogate_batch = wrap(
            bare(SwitchDSEProblem.surrogate_batch), 0, "stage2")
        SwitchDSEProblem.verify_batch = wrap(
            bare(SwitchDSEProblem.verify_batch), 1, "stage4")

    def pop(self, problem) -> tuple:
        return self.rows.pop(id(problem), ([], []))

    def snapshot(self) -> dict:
        return dict(self.work)


class Program:
    """The configured scenario with a saved trace in place of its generator."""

    def __init__(self, config: dict, mix: dict):
        from repro.api import registry

        self.base = registry[config["registry"]].override(**config["override"])
        self.search = mix.get("search")

    def scenario(self, path: str, search_seed: Optional[int] = None):
        from repro.api.scenario import SearchSpec, TraceSpec

        s = dataclasses.replace(self.base, trace=TraceSpec(path=path))
        if self.search is not None:
            s = s.override(search=SearchSpec(seed=int(search_seed),
                                             **self.search))
        return s

    def departures(self, config: dict, path: str) -> List[str]:
        """Where the program's scenario differs from what the configuration
        states; a run with any is not a sound run."""
        from repro.api.runner import build_problem

        sw = config["switch"]
        s = self.scenario(path, 0)
        problem, sla, budget = build_problem(s)
        got = {
            "n_ports": problem.request.n_ports,
            "header_fields": [[f.name, f.bits]
                              for f in problem.bound.protocol.fields],
            "routing_bits": problem.bound.routing_field.bits,
            "flit_bits": problem.flit_bits,
            "sla": {"p99_latency_ns": sla.p99_latency_ns,
                    "drop_rate": sla.drop_rate},
            "budget": dict(budget.limits),
            "delta": s.fidelity.delta,
            "top_k": s.fidelity.top_k,
            "back_annotation": s.fidelity.back_annotation,
            "verify_engine": s.fidelity.verify_engine,
        }
        return [f"{k}: program {got[k]!r}, configuration {sw[k]!r}"
                for k in got if got[k] != sw[k]]


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from the run's seed (algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def explore(program: Program, path: str, search_seed: Optional[int]):
    from repro.api import run_scenario

    return run_scenario(program.scenario(path, search_seed))


def closed_warmup(program: Program, pool: TracePool, probe: Probe,
                  warm: int, search_seeds: List[int]) -> None:
    for i in range(warm):
        rep = explore(program, pool.get(i), search_seeds[i])
        probe.pop(rep.problem)


def warm_rows(program: Program, path: str, probe: Probe,
              rows: Dict[str, List[int]]) -> None:
    """Compile the batch widths a cell's window can meet: one stage-2 and
    one stage-4 call per width named in the mix, on distinct templates."""
    from repro.api.runner import build_problem

    problem, _, _ = build_problem(program.scenario(path, 0))
    templates = problem.candidates()
    for b in rows.get("stage2", []):
        problem.surrogate_batch(templates[:b])
    for b in rows.get("stage4", []):
        problem.verify_batch(templates[:b])
    probe.pop(problem)


def closed_window(program: Program, pool: TracePool, probe: Probe, *,
                  first: int, seconds: float, sample: int, seed: int,
                  search_seeds: List[int], tracer) -> dict:
    """Whole explorations on fresh traces until ``seconds`` have passed;
    the last one runs to its end.  A window that runs out of traces ends
    there, as a failure."""
    records, failures = [], []
    kept = Reservoir(sample, stream(seed, KEEP))
    i = first
    t0 = time.perf_counter()
    with _span("bench.window"):
        while time.perf_counter() - t0 < seconds:
            try:
                path = pool.get(i)
            except PoolExhausted as e:
                failures.append(str(e))
                break
            t1 = time.perf_counter()
            try:
                with _span("bench.explore"):
                    rep = explore(program, path, search_seeds[i])
            except Exception as e:  # noqa: BLE001 - a failed exploration is counted
                failures.append(f"exploration {i}: {type(e).__name__}: {e}")
                probe.rows.clear()
                i += 1
                continue
            t2 = time.perf_counter()
            records.append({"wall_s": t2 - t1,
                            "stage2_s": rep.stage2_time_s,
                            "stage4_s": rep.stage4_time_s})
            kept.offer((i, rep, probe.pop(rep.problem)))
            i += 1
            tracer.poll()
    window_s = time.perf_counter() - t0
    return {"t0": t0, "window_s": window_s, "explorations": records,
            "attempted": i - first, "failures": failures,
            "kept": kept.items}
