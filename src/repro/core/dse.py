"""Progressive Constraint Satisfaction DSE — Algorithm 1 of the paper.

The engine is deliberately generic: a ``DSEProblem`` supplies templates,
timing, surrogate evaluation, buffer sizing and verification, and the engine
runs the paper's four stages:

  Stage 1  Static pruning        T_proc > (1+δ)·T_arrival ⇒ drop
  Stage 2  Coarse profiling      surrogate w/ infinite buffers; prune on p99 SLA
  Stage 3  Statistical sizing    d_opt from queue-occupancy histogram @ ε,
                                 aligned to physical memory; prune on resources
  Stage 4  Verification          full simulation of the sized survivors, fanned
                                 through one ``verify_batch`` call

Two concrete problems implement this interface:
  * ``repro.sim.switch_problem.SwitchDSEProblem``  — the paper's FPGA switch
  * ``repro.comm.dse_comm.CommDSEProblem``         — the TPU comm/dispatch layer
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import (TYPE_CHECKING, Any, Callable, Dict, List, Optional,
                    Sequence, Tuple)

import numpy as np

from repro.analysis.spans import note, span

from .pareto import pareto_front

if TYPE_CHECKING:                      # import cycle: search imports this module
    from .search import DesignSpace, SearchSpec

__all__ = [
    "SLA",
    "ResourceBudget",
    "VERIFY_ENGINES",
    "USE_KERNEL_MODES",
    "SurrogateResult",
    "VerifyResult",
    "DSEProblem",
    "DSEResult",
    "StageLog",
    "run_dse",
    "stage1_static",
    "stage2_screen",
    "stage3_size",
    "stage4_verify",
    "finalize_result",
    "check_index_aligned",
    "depth_for_drop_rate",
    "IncrementalDSE",
    "drain",
    "search_checkpoint_dir",
]


#: stage-4 policy vocabulary — the single source of truth shared by the
#: switch problem, the Scenario `Fidelity` spec and the CLI flag:
#: "netsim"  — every sized survivor through the batched finite-buffer sim,
#: "cycle"   — every survivor through the cycle-accurate datapath (slow),
#: "auto"    — netsim for the front, cycle-sim for the champion only
#: (via the `escalate` hook).
VERIFY_ENGINES = ("netsim", "cycle", "auto")

#: `use_kernel` knob vocabulary — shared by the switch problem, the Scenario
#: `Fidelity` spec and the CLI `--use-kernel` flag:
#: "auto" — the segmented netsim kernels when available (bit-exact oracle
#:          fallback otherwise; `SPAC_NETSIM_KERNEL=off` disables globally),
#: "on"   — force the kernel engines,
#: "off"  — force today's oracle scans (byte-identical legacy path).
USE_KERNEL_MODES = ("auto", "on", "off")


@dataclasses.dataclass(frozen=True)
class SLA:
    p99_latency_ns: float = math.inf
    drop_rate: float = 1e-3          # ε: target tail drop rate for sizing
    min_throughput_gbps: float = 0.0


@dataclasses.dataclass(frozen=True)
class ResourceBudget:
    """Generic resource vector; keys are resource names (LUT/BRAM/… or bytes)."""

    limits: Dict[str, float]

    def admits(self, usage: Dict[str, float]) -> bool:
        return all(usage.get(k, 0.0) <= v for k, v in self.limits.items())


@dataclasses.dataclass
class SurrogateResult:
    """Stage-2 output: infinite-buffer queue occupancy histogram + latencies."""

    q_occupancy: np.ndarray       # samples (or histogram support) of max queue depth
    latency_ns: np.ndarray        # per-packet latency samples
    throughput_gbps: float = 0.0
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: latency percentiles the producer already took, keyed by float q
    #: (the batched engine carries its row of ``quantiles``); empty when
    #: the producer carries none or the trace is empty
    quantiles: Dict[float, float] = dataclasses.field(default_factory=dict)

    def p(self, q: float) -> float:
        """Latency percentile ``q``: the carried value when there is one,
        else a percentile over every sample (noted as a miss)."""
        note(quantile_reads=1)
        carried = self.quantiles.get(float(q))
        if carried is not None:
            return carried
        if not self.latency_ns.size:
            return math.inf
        note(quantile_misses=1)
        return float(np.percentile(self.latency_ns, q))


@dataclasses.dataclass
class VerifyResult:
    p99_latency_ns: float
    mean_latency_ns: float
    drop_rate: float
    throughput_gbps: float
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def meets(self, sla: SLA) -> bool:
        return (
            self.p99_latency_ns <= sla.p99_latency_ns
            and self.drop_rate <= sla.drop_rate * 1.5  # discretised sizing slack
            and self.throughput_gbps >= sla.min_throughput_gbps
        )


class DSEProblem:
    """Interface Algorithm 1 runs against (override all methods)."""

    #: optional ``repro.launch.mesh.MeshSpec`` — problems whose batched
    #: stages can shard the candidate axis read it; results must be
    #: bit-identical to the serial default (None)
    mesh_spec = None

    def candidates(self) -> List[Any]:
        raise NotImplementedError

    def static_timing(self, cand) -> Tuple[float, float]:
        """Return (T_proc, T_arrival) in seconds for stage-1 pruning."""
        raise NotImplementedError

    def surrogate(self, cand) -> SurrogateResult:
        """Infinite-buffer statistical simulation (stage 2)."""
        raise NotImplementedError

    def surrogate_batch(self, cands: Sequence[Any]) -> List[SurrogateResult]:
        """Stage-2 fan-out hook: evaluate a whole candidate batch at once.

        Results must be index-aligned with ``cands``.  The default is the
        serial fallback (one ``surrogate`` call per candidate); problems with
        a vectorised surrogate override this — e.g. the switch problem fans
        candidates out through the batched JAX engine
        (``repro.sim.batched_surrogate``) so thousands of templates cost one
        jitted scan instead of thousands of Python loops.  Stage 3 consumes
        the returned occupancy samples unchanged."""
        return [self.surrogate(c) for c in cands]

    def size_buffers(self, cand, q_occupancy: np.ndarray, eps: float):
        """Map occupancy histogram to a sized candidate (stage 3)."""
        raise NotImplementedError

    def resources(self, cand) -> Dict[str, float]:
        raise NotImplementedError

    def verify(self, cand) -> VerifyResult:
        """High-fidelity simulation of the sized candidate (stage 4)."""
        raise NotImplementedError

    def verify_batch(self, cands: Sequence[Any]) -> List[VerifyResult]:
        """Stage-4 fan-out hook: verify a whole sized-candidate batch at once.

        The mirror of ``surrogate_batch`` one rung up the fidelity ladder:
        stage 3 sizes *all* explored candidates first, then the sized
        survivors fan through one call here.  Results must be index-aligned
        with ``cands``.  The default is the serial fallback (one ``verify``
        call per candidate); problems with a batched verifier override it —
        the switch problem runs the finite-buffer event simulator as one
        jitted scan with sized VOQ depths as a batch axis
        (``repro.sim.batched_netsim``), the comm problem vectorises its
        analytic fabric metrics."""
        return [self.verify(c) for c in cands]

    def space(self) -> Optional["DesignSpace"]:
        """Parameterized design space for the generational search engine.

        Where ``candidates()`` returns a *pre-built list* of templates, this
        returns per-dimension ranges (``repro.core.search.DesignSpace``) that
        NSGA-II samples — so the joint space can be combinatorially larger
        than anything worth enumerating.  Problems that support search
        override this together with ``decode``; the default (None) keeps the
        problem exhaustive-only."""
        return None

    def decode(self, assignment: Dict[str, Any]) -> Any:
        """Materialise one ``space()`` point (a name->choice dict) into a
        candidate — the inverse of a genome, used by the search engine."""
        raise NotImplementedError

    def surrogate_objectives(self, cand, sr: SurrogateResult) -> Tuple[float, float]:
        """Stage-2-fidelity (latency, primary-resource) pair for the search
        engine (minimise both).  Mirrors ``objectives`` one rung down the
        ladder: the generational engine ranks whole populations on surrogate
        results long before anything is sized or verified."""
        res = self.resources(cand)
        primary = res.get("bram", res.get("bytes_per_device", sum(res.values())))
        return (sr.p(99), float(primary))

    def escalate(self, cand, v: VerifyResult) -> Optional[VerifyResult]:
        """Optional champion escalation to a higher fidelity rung.

        Called once per DSE with the winning (candidate, verify) pair; a
        problem may re-verify the champion on a more faithful engine (e.g.
        the cycle-accurate datapath under ``verify_engine="auto"``) and
        return that result — it is attached as ``meta["escalated"]`` on the
        champion's verify, never replacing the ranking metrics (so the
        Pareto front is engine-independent).  Default: no escalation."""
        return None

    def objectives(self, cand, verify: VerifyResult) -> Tuple[float, float]:
        """(latency, primary-resource) pair for ranking/Pareto (minimise both)."""
        res = self.resources(cand)
        primary = res.get("bram", res.get("bytes_per_device", sum(res.values())))
        return (verify.p99_latency_ns, float(primary))

    def diversity_key(self, cand):
        """Architecture-family key: stage 3 verifies the best candidate of
        every family in addition to the global top-K, so a surrogate ranking
        bias cannot starve a whole scheduler/buffer family of verification."""
        return None


@dataclasses.dataclass
class StageLog:
    stage: str
    considered: int
    survived: int
    notes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DSEResult:
    best: Optional[Any]
    best_verify: Optional[VerifyResult]
    pareto: List[Tuple[Any, VerifyResult]]
    evaluated: List[Tuple[Any, VerifyResult, Dict[str, float], bool]]
    logs: List[StageLog]

    def summary(self) -> str:
        lines = [f"DSE: {len(self.evaluated)} verified, {len(self.pareto)} on Pareto front"]
        for lg in self.logs:
            lines.append(f"  [{lg.stage}] {lg.considered} -> {lg.survived}")
        if self.best is not None and self.best_verify is not None:
            lines.append(
                f"  best: {getattr(self.best, 'short', lambda: repr(self.best))()} "
                f"p99={self.best_verify.p99_latency_ns:.1f}ns "
                f"drop={self.best_verify.drop_rate:.2e} "
                f"thru={self.best_verify.throughput_gbps:.1f}Gbps"
            )
        return "\n".join(lines)


def check_index_aligned(problem: DSEProblem, results: Sequence[Any],
                        cands: Sequence[Any], hook: str) -> None:
    """One home for the batch-hook alignment error, shared by the staged
    engine and the search driver so the message can never drift."""
    if len(results) != len(cands):
        raise ValueError(
            f"{type(problem).__name__}.{hook} returned {len(results)} "
            f"results for a {len(cands)}-candidate batch (result shape "
            f"[{len(results)}] vs candidate shape [{len(cands)}]); results "
            "must be index-aligned")


def depth_for_drop_rate(q_occupancy: np.ndarray, eps: float) -> int:
    """Smallest depth d with P(occupancy > d) <= ε (stage 3 core)."""
    q = np.asarray(q_occupancy, dtype=np.float64)
    if q.size == 0:
        return 1
    d = float(np.quantile(q, 1.0 - eps, method="higher"))
    return max(1, int(math.ceil(d)))


def stage1_static(problem: DSEProblem, *, delta: float = 0.2) -> Tuple[List[Any], StageLog]:
    """Stage 1: static timing pruning over the enumerated templates."""
    with span("spac.stage1"):
        cands = list(problem.candidates())
        active = []
        for a in cands:
            t_proc, t_arrival = problem.static_timing(a)
            if t_proc <= (1.0 + delta) * t_arrival:
                active.append(a)
        note(survivors=len(active))
    return active, StageLog("stage1-static", len(cands), len(active))


def stage2_screen(
    problem: DSEProblem,
    active: Sequence[Any],
    sla: SLA,
    *,
    surrogates: Optional[Sequence[SurrogateResult]] = None,
) -> Tuple[List[Tuple[Any, SurrogateResult]], StageLog]:
    """Stage 2: coarse-grained profiling + SLA screening.

    ``surrogates`` are the stage-2 results, index-aligned with ``active``:
    ``IncrementalDSE`` passes the rows its clients evaluated, possibly in
    calls shared with other machines' rows.  When absent, the problem's
    ``surrogate_batch`` hook runs here.
    """
    active = list(active)
    srs = list(surrogates) if surrogates is not None else problem.surrogate_batch(active)
    with span("spac.screen"):
        check_index_aligned(problem, srs, active, "surrogate_batch")
        valid: List[Tuple[Any, SurrogateResult]] = []
        for a, sr in zip(active, srs):
            if sr.p(99) <= sla.p99_latency_ns and sr.throughput_gbps >= sla.min_throughput_gbps:
                valid.append((a, sr))
        note(survivors=len(valid))
    return valid, StageLog("stage2-surrogate", len(active), len(valid))


def stage3_size(
    problem: DSEProblem,
    valid: Sequence[Tuple[Any, SurrogateResult]],
    sla: SLA,
    budget: ResourceBudget,
    *,
    top_k: int = 8,
) -> Tuple[List[Tuple[Any, Dict[str, float]]], int]:
    """Stage 3 alone: exploration, statistical sizing, resource pruning.

    TopKLatency: explore the K best candidates by surrogate p99, plus the
    best of each architecture family (diversity-preserving).  Every explored
    candidate is sized from its occupancy histogram and priced; survivors of
    the budget come back as index-stable ``(sized, resources)`` pairs so the
    whole batch can fan through one ``verify_batch`` call.
    """
    with span("spac.stage3"):
        sized, n_explored = _size(problem, valid, sla, budget, top_k)
        note(survivors=len(sized))
    return sized, n_explored


def _size(problem, valid, sla, budget, top_k):
    valid = sorted(valid, key=lambda av: av[1].p(99))
    explored = list(valid[: top_k if top_k > 0 else len(valid)])
    seen_keys = {id(a) for a, _ in explored}
    families = {}
    for a, sr in valid:
        k = problem.diversity_key(a)
        if k is not None and k not in families:
            families[k] = (a, sr)
    for a, sr in families.values():
        if id(a) not in seen_keys:
            explored.append((a, sr))
    sized: List[Tuple[Any, Dict[str, float]]] = []
    for a, sr in explored:
        s = problem.size_buffers(a, sr.q_occupancy, sla.drop_rate)
        if s is None:
            continue
        res = problem.resources(s)
        if not budget.admits(res):
            continue
        sized.append((s, res))
    return sized, len(explored)


def stage4_verify(
    problem: DSEProblem,
    sized: Sequence[Tuple[Any, Dict[str, float]]],
    sla: SLA,
    *,
    verifies: Optional[Sequence[VerifyResult]] = None,
) -> Tuple[List[Tuple[Any, VerifyResult, Dict[str, float], bool]],
           Optional[Any], Optional[VerifyResult]]:
    """Stage 4: rank the sized survivors on their verification results.

    ``verifies`` are the verification results, index-aligned with
    ``sized``: ``IncrementalDSE`` passes the rows its clients evaluated,
    possibly in calls shared with other machines' rows.  When absent, the
    problem's ``verify_batch`` hook runs here.  The champion is escalated via
    ``problem.escalate`` (a no-op by default)."""
    cands = [a for a, _ in sized]
    vs = list(verifies) if verifies is not None else problem.verify_batch(cands)
    check_index_aligned(problem, vs, cands, "verify_batch")
    evaluated: List[Tuple[Any, VerifyResult, Dict[str, float], bool]] = []
    best: Optional[Any] = None
    best_v: Optional[VerifyResult] = None
    for (a, res), v in zip(sized, vs):
        feasible = v.meets(sla)
        evaluated.append((a, v, res, feasible))
        if feasible:
            if best_v is None or problem.objectives(a, v) < problem.objectives(best, best_v):
                best, best_v = a, v
    if best is not None:
        esc = problem.escalate(best, best_v)
        if esc is not None:
            best_v.meta["escalated"] = esc
    return evaluated, best, best_v


def finalize_result(
    problem: DSEProblem,
    evaluated: List[Tuple[Any, VerifyResult, Dict[str, float], bool]],
    best: Optional[Any],
    best_v: Optional[VerifyResult],
    logs: List[StageLog],
) -> DSEResult:
    """Rank the verified candidates into a Pareto front and assemble the result."""
    feas = [(a, v) for a, v, _, ok in evaluated if ok] or [(a, v) for a, v, _, _ in evaluated]
    front = pareto_front(feas, key=lambda av: problem.objectives(av[0], av[1])) if feas else []
    return DSEResult(best=best, best_verify=best_v, pareto=front, evaluated=evaluated, logs=logs)


def run_dse(
    problem: DSEProblem,
    sla: SLA,
    budget: ResourceBudget,
    *,
    delta: float = 0.2,
    top_k: int = 8,
    verbose: bool = False,
    search: Optional["SearchSpec"] = None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    mesh=None,
) -> DSEResult:
    """Algorithm 1: Progressive Constraint Satisfaction.

    One ``IncrementalDSE`` drained by ``drain``, the same four steps every
    entry point runs (``repro.api.run_scenario``, ``run_campaign`` and the
    serve engine batch the same machine's rows).

    ``search`` replaces the exhaustive stage-1/2 enumeration with the
    generational NSGA-II engine over ``problem.space()`` (seeded, resumable
    — see ``repro.core.search``): the engine's final archive plays the role
    of the screened ``valid`` set, and stages 3-4 run unchanged, so
    verification semantics are identical either way.  ``checkpoint_dir`` /
    ``resume`` control search-state persistence (``checkpoint_dir`` defaults
    to ``search.checkpoint_dir``).

    ``mesh`` is an optional ``repro.launch.mesh.MeshSpec`` (or device count)
    set on ``problem.mesh_spec``: batched stages shard their candidate axis
    across the device mesh, bit-identical to the serial default.  The mesh
    never enters search state — checkpoints written on N devices resume on M.
    """
    if mesh is not None:
        from repro.launch.mesh import MeshSpec
        problem.mesh_spec = MeshSpec.coerce(mesh)
    m = IncrementalDSE(problem, sla, budget, delta=delta, top_k=top_k,
                       search=search, checkpoint_dir=checkpoint_dir,
                       resume=resume)
    drain([m], "surrogate")
    m.advance()
    drain([m], "verify")
    m.advance()
    if verbose:
        for lg in m.result.logs:
            print(lg)
    return m.result


def search_checkpoint_dir(search: Optional["SearchSpec"],
                          nest: Optional[str] = None) -> Optional[str]:
    """Where a search keeps its state: ``search.checkpoint_dir``, or, for a
    campaign, a directory under it named ``nest`` (the scenario's name), so
    one ``checkpoint_dir`` serves the whole sweep."""
    if search is None or not search.checkpoint_dir:
        return None
    return (os.path.join(search.checkpoint_dir, nest) if nest
            else search.checkpoint_dir)


class IncrementalDSE:
    """Algorithm 1 for one problem, as a state machine its owner batches.

    This is the one place that knows the order of the stages.  Every entry
    point (``run_dse``, ``repro.api.run_scenario``, ``run_campaign`` and the
    serve engine) is a client that evaluates the machine's ``pending`` rows
    through the problem's batch hooks, hands them back with ``feed()`` and
    calls ``advance()`` once a queue has drained:

    * stage 1 runs at construction; ``kind`` is ``"surrogate"`` and
      ``pending`` holds the stage-2 rows;
    * ``feed()`` stores index-aligned results for any *prefix* of
      ``pending``.  Under a ``SearchSpec``, ``pending`` is the current
      generation's population (``repro.core.search.SearchDriver``), and a
      fully fed generation is told to the engine, which asks the next one;
      once the engine is done its ``finalize()`` runs inside ``feed()``, so
      all search work stays stage-2 work;
    * ``advance()`` on a drained stage-2 queue runs the screen and stage-3
      sizing: ``kind`` becomes ``"verify"`` and ``pending`` holds the sized
      rows.  On a drained stage-4 queue it ranks the verified rows,
      escalates the champion and builds the front under ``spac.finalize``:
      ``kind`` becomes ``"done"`` and ``result`` is set.

    Feeding results chunk at a time, or together with other machines' rows
    in one call, is exact because both batch hooks are row-independent, so
    every client gets the same ``DSEResult``.  ``stage2_time_s`` and
    ``stage4_time_s`` hold this machine's share of the batched calls'
    seconds (its share of their rows), ``host_time_s`` the seconds of its
    own construction and ``advance()`` calls.
    """

    def __init__(self, problem: DSEProblem, sla: SLA, budget: ResourceBudget,
                 *, delta: float = 0.2, top_k: int = 8,
                 search: Optional["SearchSpec"] = None,
                 checkpoint_dir: Optional[str] = None, resume: bool = False):
        t0 = time.perf_counter()
        self.problem = problem
        self.sla = sla
        self.budget = budget
        self.top_k = top_k
        self.kind = "surrogate"
        self.stage2_candidates = 0      # rows this machine fanned out
        self.stage4_candidates = 0      # sized rows verified
        self.stage2_time_s = 0.0
        self.stage4_time_s = 0.0
        self._logs: List[StageLog] = []
        self._pending: List[Any] = []
        self._fed: List[Any] = []
        self._valid: Optional[List[Tuple[Any, SurrogateResult]]] = None
        self._sized: List[Tuple[Any, Dict[str, float]]] = []
        self._n_explored = 0
        self._result: Optional[DSEResult] = None
        self._driver = None             # the search, until it is finalized
        if search is not None:
            from .search import SearchDriver
            self._driver = SearchDriver(problem, search, sla, delta=delta,
                                        checkpoint_dir=checkpoint_dir,
                                        resume=resume)
            self._ask()
        else:
            self._active, log1 = stage1_static(problem, delta=delta)
            self._logs.append(log1)
            self._pending = list(self._active)
            self.stage2_candidates = len(self._active)
        self.host_time_s = time.perf_counter() - t0

    # -------------------------------------------------------------- queries
    @property
    def done(self) -> bool:
        return self._result is not None

    @property
    def result(self) -> DSEResult:
        if self._result is None:
            raise ValueError("IncrementalDSE still has pending work")
        return self._result

    @property
    def pending(self) -> List[Any]:
        """Candidates awaiting evaluation at the current ``kind`` fidelity.
        The owner may evaluate any prefix and ``feed()`` it back."""
        return list(self._pending)

    # ---------------------------------------------------------- transitions
    def _ask(self) -> None:
        """Search: move to the next non-empty population (an ask can come
        back empty when every genome was pruned or answered from the
        phenotype cache — tell the engine and move on); finalize the search
        once the engine is done."""
        while not self._driver.done:
            cands = self._driver.ask_candidates()
            if cands:
                self._pending = list(cands)
                return
            self._driver.tell_candidates([])
        outcome = self._driver.finalize()
        self._valid = outcome.valid
        self._logs.append(outcome.log)
        # finalize()'s archive re-surrogation (resume path) counts as
        # stage-2 fan-out
        self.stage2_candidates = outcome.surrogate_rows
        self._driver = None

    def feed(self, results: Sequence[Any]) -> None:
        """Hand back index-aligned results for the first ``len(results)``
        entries of ``pending``."""
        if self.done:
            raise ValueError("IncrementalDSE is already finished")
        results = list(results)
        if len(results) > len(self._pending):
            raise ValueError(
                f"fed {len(results)} results for {len(self._pending)} "
                "pending candidates; feed at most the pending prefix")
        self._fed.extend(results)
        del self._pending[:len(results)]
        if not self._pending and self._driver is not None:
            self._driver.tell_candidates(self._fed)
            self._fed = []
            self._ask()

    def advance(self) -> None:
        """Run the host stage that follows a drained queue: the screen and
        stage 3 after stage 2, the ranking and the result after stage 4."""
        if self.done or self._pending:
            raise ValueError("advance() needs a drained queue of an "
                             "unfinished IncrementalDSE")
        t0 = time.perf_counter()
        if self.kind == "surrogate":
            if self._valid is None:
                self._valid, log2 = stage2_screen(
                    self.problem, self._active, self.sla,
                    surrogates=self._fed)
                self._logs.append(log2)
            self._sized, self._n_explored = stage3_size(
                self.problem, self._valid, self.sla, self.budget,
                top_k=self.top_k)
            self.kind = "verify"
            self._pending = [a for a, _ in self._sized]
            self._fed = []
            self.stage4_candidates = len(self._sized)
        else:
            with span("spac.finalize"):
                evaluated, best, best_v = stage4_verify(
                    self.problem, self._sized, self.sla, verifies=self._fed)
                log3 = StageLog("stage3-sizing+verify", self._n_explored,
                                len(self._sized))
                self._result = finalize_result(
                    self.problem, evaluated, best, best_v, self._logs + [log3])
            self.kind = "done"
        self.host_time_s += time.perf_counter() - t0


def drain(machines: Sequence[IncrementalDSE], kind: str,
          key: Callable[[IncrementalDSE], Any] = id) -> Tuple[int, int, float]:
    """Evaluate every pending row of ``kind`` (``"surrogate"`` or
    ``"verify"``) across ``machines``; returns (calls, rows, seconds).

    Each round makes one ``surrogate_batch`` or ``verify_batch`` call (span
    ``spac.stage2`` or ``spac.stage4``) per group key over the rows of the
    machines that ``key`` puts in that group (by default each machine is
    its own group), through the first member's problem, and feeds each
    machine its slice back; rounds repeat while a search asks for further
    generations.  Each call's seconds go to its members' ``stage2_time_s``
    or ``stage4_time_s`` by their share of its rows."""
    hook, name = (("surrogate_batch", "spac.stage2") if kind == "surrogate"
                  else ("verify_batch", "spac.stage4"))
    calls = rows = 0
    seconds = 0.0
    while True:
        groups: Dict[Any, List[IncrementalDSE]] = {}
        for m in machines:
            if m.kind == kind and m._pending:
                groups.setdefault(key(m), []).append(m)
        if not groups:
            return calls, rows, seconds
        for members in groups.values():
            parts = [m.pending for m in members]
            batch = [c for p in parts for c in p]
            problem = members[0].problem
            with span(name) as sp:
                out = list(getattr(problem, hook)(batch))
            check_index_aligned(problem, out, batch, hook)
            off = 0
            for m, p in zip(members, parts):
                share = sp.seconds * (len(p) / len(batch))
                if kind == "surrogate":
                    m.stage2_time_s += share
                else:
                    m.stage4_time_s += share
                m.feed(out[off:off + len(p)])
                off += len(p)
            calls += 1
            rows += len(batch)
            seconds += sp.seconds
