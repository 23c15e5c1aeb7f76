"""Scenario execution: one spec in, a structured ``ScenarioReport`` out —
and campaign-level fan-out that batches stage 2 across scenarios.

``run_scenario`` is semantically identical to the legacy hand-wired path
(``optimize_switch`` / ``autotune_moe``): it builds the protocol, binds it,
materialises the trace, instantiates the domain's ``DSEProblem`` and runs
Algorithm 1 with the scenario's SLA/budget/fidelity.  The legacy wrappers
remain as thin compatibility shims over the same machinery.

Both entry points are clients of ``repro.core.dse.IncrementalDSE``, the one
code that sequences the stages: they drain its stage-2 rows, advance it,
drain its stage-4 rows and advance it again (``repro.core.dse.drain``).
``run_campaign`` builds one machine per scenario and drains them together:
scenarios that share a trace and a bound protocol share one jitted call at
both batched stages, and every scenario reuses a cached trace + feature
analysis.  The campaign report carries aggregate stage-2 *and* stage-4
throughput (candidates/sec across the whole campaign).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from repro.analysis.spans import note, span
from repro.core.binding import BoundProtocol, bind
from repro.core.dse import (DSEProblem, DSEResult, IncrementalDSE,
                            ResourceBudget, SLA, VerifyResult, drain,
                            search_checkpoint_dir)

from .registry import registry
from .scenario import MeshSpec, Scenario

__all__ = ["ScenarioReport", "CampaignReport", "build_bound", "build_problem",
           "run_scenario", "run_campaign"]


# --------------------------------------------------------------------------
# problem construction
# --------------------------------------------------------------------------

def build_bound(scenario: Scenario) -> BoundProtocol:
    """Protocol spec → built ``Protocol`` → semantic binding (§III-A)."""
    return bind(scenario.protocol.build(), scenario.semantic_binding(),
                flit_bits=scenario.flit_bits)


def _validate_addressing(scenario: Scenario, bound: BoundProtocol) -> None:
    """Fail at scenario-build time if an address field cannot address every
    port — ``compressed_protocol(addr_bits=2)`` on an 8-port scenario used to
    run (and silently alias destinations via ``dst % n_ports``).  Same rule
    the co-design stage-1 prune applies (``address_width_error``).  On a
    fabric scenario the routing field must address every *host in the
    topology*, not one switch's ports (``spac check`` SPAC106)."""
    from repro.core.dsl import address_width_error
    n = (scenario.topology.build().n_hosts if scenario.topology is not None
         else scenario.arch.n_ports)
    for sem in ("routing_key", "src_key"):
        if not bound.has(sem):
            continue
        f = bound.protocol.field(bound.semantics[sem])
        err = address_width_error(sem, f.name, f.bits, n)
        if err is not None:
            raise ValueError(
                f"scenario {scenario.name!r}: protocol "
                f"{bound.protocol.name!r} {err}; widen the field")


def _default_budget(scenario: Scenario) -> ResourceBudget:
    if scenario.domain == "comm":
        return ResourceBudget({"bytes_per_device": 4e9})
    from repro.sim.resources import ALVEO_U45N
    if scenario.topology is not None:
        # fabric resources are summed over every switch; the default budget
        # is one FPGA card per node
        n_nodes = sum(t.n_nodes for t in scenario.topology.build().tiers)
        return ResourceBudget({k: v * n_nodes for k, v in ALVEO_U45N.items()})
    return ResourceBudget(dict(ALVEO_U45N))


def _build_comm_problem(scenario: Scenario) -> DSEProblem:
    import jax
    import jax.numpy as jnp

    from repro.comm.dse_comm import CommDSEProblem
    from repro.launch.mesh import make_mesh
    from repro.models import SINGLE_POD_PLAN, ModelConfig
    from repro.models.moe import init_moe

    c = scenario.comm
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(name=scenario.name, family="moe", n_layers=1,
                      d_model=c.d_model, n_heads=c.n_heads,
                      n_kv_heads=c.n_kv_heads, d_ff=c.d_ff, vocab=c.vocab,
                      moe_experts=c.moe_experts, moe_topk=c.moe_topk,
                      router=c.router)
    plan = SINGLE_POD_PLAN
    params, _ = init_moe(jax.random.PRNGKey(c.seed), cfg, plan)
    x = jax.random.normal(jax.random.PRNGKey(c.seed + 1),
                          (c.batch, c.seq, c.d_model), jnp.bfloat16)
    return CommDSEProblem(params, cfg, plan, mesh, x, model_tp=c.model_tp)


def build_problem(
    scenario: Scenario,
    *,
    trace=None,
    features=None,
    mesh=None,
) -> Tuple[DSEProblem, SLA, ResourceBudget]:
    """Materialise the scenario into a ready-to-run ``DSEProblem``.

    ``trace``/``features`` let a campaign hand scenarios that share a
    ``TraceSpec`` one built trace and one feature analysis.  ``mesh``
    (a ``MeshSpec`` or device count) overrides ``scenario.mesh``; either
    shards the batched stages across the device mesh, with results
    bit-identical to the serial default.
    """
    with span("spac.build"):
        return _build_problem(scenario, trace, features, mesh)


def _build_problem(scenario: Scenario, trace, features, mesh):
    mesh = MeshSpec.coerce(mesh) if mesh is not None else scenario.mesh
    budget = scenario.budget or _default_budget(scenario)
    if scenario.domain == "comm":
        return _build_comm_problem(scenario), scenario.sla, budget
    from repro.sim.switch_problem import SwitchDSEProblem
    tr = trace if trace is not None else scenario.trace.build()
    note(events=len(tr))
    if scenario.topology is not None:
        from repro.fabric import FabricDSEProblem
        topo = scenario.topology.build()
        if scenario.co_design:
            if scenario.search is None:
                raise ValueError(
                    f"scenario {scenario.name!r}: co_design joint spaces are "
                    "generational-search territory — set a SearchSpec "
                    "(spac run --co-design --search nsga2)")
            problem = FabricDSEProblem(
                topo, scenario.arch, None, tr,
                back_annotation=scenario.fidelity.back_annotation,
                features=features,
                verify_engine=scenario.fidelity.verify_engine,
                use_kernel=scenario.fidelity.use_kernel,
                protocol_space=scenario.protocol.space(),
                binding=scenario.semantic_binding(),
                flit_bits=scenario.flit_bits,
                mesh=mesh)
            return problem, scenario.sla, budget
        bound = build_bound(scenario)
        _validate_addressing(scenario, bound)
        problem = FabricDSEProblem(
            topo, scenario.arch, bound, tr,
            back_annotation=scenario.fidelity.back_annotation,
            features=features,
            verify_engine=scenario.fidelity.verify_engine,
            use_kernel=scenario.fidelity.use_kernel,
            mesh=mesh)
        return problem, scenario.sla, budget
    if scenario.co_design:
        if scenario.search is None:
            raise ValueError(
                f"scenario {scenario.name!r}: co_design joint spaces are "
                "generational-search territory — set a SearchSpec "
                "(spac run --co-design --search nsga2)")
        problem = SwitchDSEProblem(
            scenario.arch, None, tr,
            back_annotation=scenario.fidelity.back_annotation,
            features=features,
            verify_engine=scenario.fidelity.verify_engine,
            use_kernel=scenario.fidelity.use_kernel,
            protocol_space=scenario.protocol.space(),
            binding=scenario.semantic_binding(),
            flit_bits=scenario.flit_bits,
            mesh=mesh)
        return problem, scenario.sla, budget
    bound = build_bound(scenario)
    _validate_addressing(scenario, bound)
    problem = SwitchDSEProblem(
        scenario.arch, bound, tr,
        back_annotation=scenario.fidelity.back_annotation,
        features=features,
        verify_engine=scenario.fidelity.verify_engine,
        use_kernel=scenario.fidelity.use_kernel,
        mesh=mesh)
    return problem, scenario.sla, budget


# --------------------------------------------------------------------------
# reports
# --------------------------------------------------------------------------

def _short(cand: Any) -> str:
    fn = getattr(cand, "short", None)
    return fn() if callable(fn) else repr(cand)


def _verify_dict(v: VerifyResult) -> Dict[str, Any]:
    d: Dict[str, Any] = {
        "p99_latency_ns": float(v.p99_latency_ns),
        "mean_latency_ns": float(v.mean_latency_ns),
        "drop_rate": float(v.drop_rate),
        "throughput_gbps": float(v.throughput_gbps),
    }
    fab = v.meta.get("fabric") if isinstance(v.meta, dict) else None
    if fab is not None:
        # end-to-end multi-hop metrics the single-switch path cannot express
        d["fabric"] = {
            "p50_latency_ns": float(fab["p50_latency_ns"]),
            "max_hops": int(fab["max_hops"]),
            "mean_hops": float(fab["mean_hops"]),
            "per_tier_drops": [int(x) for x in fab["per_tier_drops"]],
        }
    return d


def _protocol_dict(bound: Optional[BoundProtocol]) -> Optional[Dict[str, Any]]:
    """The winning wire layout, serialized field-by-field (report/golden)."""
    if bound is None:
        return None
    p = bound.protocol
    return {
        "name": p.name,
        "header_bits": int(p.header_bits),
        "header_bytes": int(p.header_bytes),
        "fields": [{"name": f.name, "bits": f.bits, "semantic": f.semantic}
                   for f in p.fields],
    }


@dataclasses.dataclass
class ScenarioReport:
    """Structured outcome of one scenario: Pareto front, best arch, verify
    metrics, resource report, stage logs.  ``problem``/``result`` are the
    live objects for further poking; ``to_dict()`` is the serializable view."""

    scenario: Scenario
    result: DSEResult
    problem: DSEProblem
    wall_time_s: float
    stage2_candidates: int = 0
    stage2_time_s: float = 0.0
    stage4_candidates: int = 0
    stage4_time_s: float = 0.0

    @classmethod
    def of(cls, scenario: Scenario, machine: IncrementalDSE,
           wall_time_s: float) -> "ScenarioReport":
        """The report of a finished machine: its result, candidate counts
        and stage times."""
        return cls(scenario=scenario, result=machine.result,
                   problem=machine.problem, wall_time_s=wall_time_s,
                   stage2_candidates=machine.stage2_candidates,
                   stage2_time_s=machine.stage2_time_s,
                   stage4_candidates=machine.stage4_candidates,
                   stage4_time_s=machine.stage4_time_s)

    @property
    def best(self) -> Optional[Any]:
        return self.result.best

    @property
    def best_verify(self) -> Optional[VerifyResult]:
        return self.result.best_verify

    @property
    def pareto(self) -> List[Tuple[Any, VerifyResult]]:
        return self.result.pareto

    @property
    def resources(self) -> Dict[str, float]:
        if self.result.best is None:
            return {}
        return {k: float(v)
                for k, v in self.problem.resources(self.result.best).items()}

    @property
    def stage2_cands_per_sec(self) -> float:
        return self.stage2_candidates / max(self.stage2_time_s, 1e-12)

    @property
    def best_bound(self) -> Optional[BoundProtocol]:
        """The winning design's bound protocol: the co-design candidate's own
        decoded layout, or the scenario's fixed protocol (switch domain)."""
        if self.result.best is None:
            return None
        own = getattr(self.result.best, "bound", None)
        return own if own is not None else getattr(self.problem, "bound", None)

    def summary(self) -> str:
        head = (f"scenario {self.scenario.name!r} [{self.scenario.domain}] "
                f"({self.wall_time_s:.2f}s)")
        lines = [head, self.result.summary()]
        bound = self.best_bound
        if bound is not None and self.scenario.co_design:
            p = bound.protocol
            lines.append(
                f"  protocol: {p.name} — {p.header_bits} header bits "
                f"({p.header_bytes} B on the wire)")
        res = self.resources
        if res:
            lines.append("  resources: " + " ".join(
                f"{k}={v:,.0f}" for k, v in sorted(res.items()) if k != "bram"))
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "scenario": self.scenario.to_dict(),
            "best": _short(self.result.best) if self.result.best is not None else None,
            "best_verify": (_verify_dict(self.result.best_verify)
                            if self.result.best_verify is not None else None),
            "best_protocol": _protocol_dict(self.best_bound),
            "resources": self.resources,
            "pareto": [
                {"candidate": _short(a), **_verify_dict(v)}
                for a, v in self.result.pareto
            ],
            "stages": [
                {"stage": lg.stage, "considered": lg.considered,
                 "survived": lg.survived, "notes": list(lg.notes)}
                for lg in self.result.logs
            ],
            "n_verified": len(self.result.evaluated),
            "wall_time_s": self.wall_time_s,
            "stage2_candidates": self.stage2_candidates,
            "stage2_time_s": self.stage2_time_s,
            "stage4_candidates": self.stage4_candidates,
            "stage4_time_s": self.stage4_time_s,
        }


@dataclasses.dataclass
class CampaignReport:
    """Per-scenario reports + aggregate batched stage-2/4 throughput."""

    name: str
    reports: List[ScenarioReport]
    stage2_candidates: int
    stage2_time_s: float
    stage2_batches: int
    shared_trace_scenarios: int      # scenarios that reused a cached trace
    wall_time_s: float
    stage4_candidates: int = 0
    stage4_time_s: float = 0.0
    stage4_batches: int = 0

    @property
    def stage2_cands_per_sec(self) -> float:
        return self.stage2_candidates / max(self.stage2_time_s, 1e-12)

    @property
    def stage4_cands_per_sec(self) -> float:
        return self.stage4_candidates / max(self.stage4_time_s, 1e-12)

    def __getitem__(self, name: str) -> ScenarioReport:
        for r in self.reports:
            if r.scenario.name == name:
                return r
        raise KeyError(name)

    def summary(self) -> str:
        lines = [f"campaign {self.name!r}: {len(self.reports)} scenarios "
                 f"in {self.wall_time_s:.2f}s"]
        for r in self.reports:
            best = _short(r.best) if r.best is not None else "infeasible"
            v = r.best_verify
            tail = (f" p99={v.p99_latency_ns:.0f}ns drop={v.drop_rate:.1e}"
                    if v is not None else "")
            lines.append(f"  {r.scenario.name:16s} -> {best}{tail}")
        lines.append(
            f"  stage-2 fan-out: {self.stage2_candidates} candidates in "
            f"{self.stage2_batches} batched calls, {self.stage2_time_s*1e3:.1f}ms "
            f"({self.stage2_cands_per_sec:.0f} cand/s aggregate; "
            f"{self.shared_trace_scenarios} scenario(s) shared a trace)")
        lines.append(
            f"  stage-4 fan-out: {self.stage4_candidates} sized candidates in "
            f"{self.stage4_batches} batched calls, {self.stage4_time_s*1e3:.1f}ms "
            f"({self.stage4_cands_per_sec:.0f} cand/s verify aggregate)")
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "scenarios": [r.to_dict() for r in self.reports],
            "stage2_candidates": self.stage2_candidates,
            "stage2_time_s": self.stage2_time_s,
            "stage2_cands_per_sec": self.stage2_cands_per_sec,
            "stage2_batches": self.stage2_batches,
            "stage4_candidates": self.stage4_candidates,
            "stage4_time_s": self.stage4_time_s,
            "stage4_cands_per_sec": self.stage4_cands_per_sec,
            "stage4_batches": self.stage4_batches,
            "shared_trace_scenarios": self.shared_trace_scenarios,
            "wall_time_s": self.wall_time_s,
        }


# --------------------------------------------------------------------------
# execution
# --------------------------------------------------------------------------

def run_scenario(scenario: Union[Scenario, str], *, verbose: bool = False,
                 resume: bool = False, mesh=None) -> ScenarioReport:
    """One spec in, verified Pareto front out (the quickstart in one call).

    One ``IncrementalDSE`` drained as ``run_dse`` drains it;
    ``tests/test_api.py`` asserts the stage logs and Pareto front stay
    identical to the legacy ``optimize_switch`` → ``run_dse`` path, so the
    two cannot silently diverge.  ``stage2_time_s`` is the stage-2 call's
    seconds, or under a search the whole ``spac.search`` span's: the
    machine's construction and every generation's ask, call and tell.

    With ``scenario.search`` set, stages 1-2 are replaced by the seeded
    generational NSGA-II engine (``repro.core.search``); the final archive
    feeds the identical stage-3/4 ladder.  ``resume`` continues a
    checkpointed search from ``search.checkpoint_dir``.

    ``mesh`` (a ``MeshSpec`` / device count, winning over ``scenario.mesh``)
    shards the batched stages across the device mesh without entering the
    report's scenario dict — reports, including the golden snapshots, are
    mesh-invariant.
    """
    if isinstance(scenario, str):
        scenario = registry[scenario]
    fid = scenario.fidelity
    searching = scenario.search is not None
    with span("spac.explore", scenario=scenario.name) as root:
        problem, sla, budget = build_problem(scenario, mesh=mesh)
        stage2 = span("spac.search") if searching else contextlib.nullcontext()
        with stage2 as s2:
            m = IncrementalDSE(problem, sla, budget, delta=fid.delta,
                               top_k=fid.top_k, search=scenario.search,
                               resume=resume)
            drain([m], "surrogate")
        if searching:
            m.stage2_time_s = s2.seconds
        m.advance()
        drain([m], "verify")
        m.advance()
        if verbose:
            for lg in m.result.logs:
                print(lg)
        return ScenarioReport.of(scenario, m, root.seconds)


def _switch_group_key(s: Scenario) -> str:
    """Scenarios share one batched stage-2 call iff this key matches: the
    batched engine takes one (trace, bound protocol, back-annotation) tuple."""
    return json.dumps({
        "trace": s.trace.to_dict(),
        "protocol": s.protocol.to_dict(),
        "flit_bits": s.flit_bits,
        "binding": s.binding,
        "back_annotation": s.fidelity.back_annotation,
        "use_kernel": s.fidelity.use_kernel,
        "co_design": s.co_design,
        # a fabric problem's batched calls evaluate per-tier designs over a
        # topology-specific hop decomposition — only identical topologies
        # (incl. the single-switch None) may share one call
        "topology": (s.topology.to_dict() if s.topology is not None else None),
    }, sort_keys=True)


def _verify_group_key(s: Scenario) -> str:
    """Scenarios share one batched stage-4 call iff this key matches: the
    stage-2 key plus the verify engine (sized candidates from two scenarios
    may ride one jitted netsim scan only if the same rung verifies both)."""
    return (_switch_group_key(s) + "|" + s.fidelity.verify_engine
            + "|" + s.fidelity.use_kernel)


def run_campaign(
    scenarios: Sequence[Union[Scenario, str]],
    *,
    name: str = "campaign",
    verbose: bool = False,
    resume: bool = False,
    mesh=None,
) -> CampaignReport:
    """Run many scenarios with shared trace analysis and batched stage 2.

    One ``IncrementalDSE`` per scenario, drained together.  Per-scenario
    results are identical to ``run_scenario`` (candidates of the batched
    engine are row-independent), so a campaign is never a fidelity
    trade-off — only a throughput one.  Each scenario's ``wall_time_s`` is
    its machine's own host seconds plus its share of the batched calls.

    Scenarios carrying a ``search`` spec run their generational engines in
    *lockstep*: each round, every active engine's pending population joins
    its group's single batched surrogate call (groups share a trace + bound
    protocol exactly as in exhaustive stage 2, but search and exhaustive
    rows never share a call), so N searching scenarios still cost one
    jitted call per group per generation.  ``resume`` continues each
    scenario's checkpointed search from
    ``search.checkpoint_dir/<scenario name>``.

    ``mesh`` (a ``MeshSpec`` / device count, winning over each scenario's
    own ``mesh``) shards every group's batched stage-2/stage-4 call over the
    device mesh.  A ``scenario_axis > 1`` spreads the candidate axis over a
    second, data-parallel mesh dimension as well, so a group's concatenated
    per-scenario candidate blocks land on different device groups — results
    stay bit-identical to the serial path either way.
    """
    scns = [registry[s] if isinstance(s, str) else s for s in scenarios]
    if not scns:
        raise ValueError("run_campaign needs at least one scenario")
    with span("spac.campaign", scenarios=len(scns)) as root:
        # ---- build: share built traces + feature analysis across scenarios
        from repro.core.features import analyze
        traces: Dict[str, Tuple[Any, Any]] = {}
        built = []
        shared = 0
        with span("spac.build"):
            for s in scns:
                if s.domain != "switch":
                    built.append(build_problem(s, mesh=mesh))
                    continue
                tkey = s.trace.key()
                shared += tkey in traces
                if tkey not in traces:
                    tr = s.trace.build()
                    traces[tkey] = (tr, analyze(tr))
                tr, feats = traces[tkey]
                built.append(build_problem(s, trace=tr, features=feats,
                                           mesh=mesh))

        machines: List[IncrementalDSE] = []
        keys: Dict[int, Tuple[str, str]] = {}   # id -> (stage-2, stage-4 key)
        for i, (s, (problem, sla, budget)) in enumerate(zip(scns, built)):
            m = IncrementalDSE(
                problem, sla, budget, delta=s.fidelity.delta,
                top_k=s.fidelity.top_k, search=s.search,
                checkpoint_dir=search_checkpoint_dir(s.search, s.name),
                resume=resume)
            machines.append(m)
            solo = s.domain != "switch"
            keys[id(m)] = (
                (f"solo-{i}" if solo else _switch_group_key(s))
                + ("|search" if s.search is not None else ""),
                f"solo-{i}" if solo else _verify_group_key(s))

        calls2, rows2, secs2 = drain(machines, "surrogate",
                                     lambda m: keys[id(m)][0])
        for m in machines:
            m.advance()
        calls4, rows4, secs4 = drain(machines, "verify",
                                     lambda m: keys[id(m)][1])
        for m in machines:
            m.advance()

        reports: List[ScenarioReport] = []
        for s, m in zip(scns, machines):
            if verbose:
                for lg in m.result.logs:
                    print(f"[{s.name}] {lg}")
            reports.append(ScenarioReport.of(
                s, m, m.host_time_s + m.stage2_time_s + m.stage4_time_s))
        return CampaignReport(
            name=name,
            reports=reports,
            stage2_candidates=rows2,
            stage2_time_s=secs2,
            stage2_batches=calls2,
            stage4_candidates=rows4,
            stage4_time_s=secs4,
            stage4_batches=calls4,
            shared_trace_scenarios=shared,
            wall_time_s=root.seconds,
        )
