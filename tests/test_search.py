"""Generational NSGA-II search engine: determinism matrix, checkpoint/resume,
search-quality acceptance, CLI surface.

The determinism contract under test (ISSUE 4):

  * same ``SearchSpec.seed`` ⇒ bit-identical front and ``DSEResult``,
  * fresh-vs-resumed-from-checkpoint runs are bit-identical,
  * ``verify_engine="netsim"`` and ``"auto"`` produce the identical Pareto
    front (escalation only annotates the champion's meta),
  * a checkpoint round-trip restores the RNG state exactly.

Acceptance bar: on the enlarged (>=1024-point) hft space, NSGA-II reaches
>=95% of the exhaustive front's hypervolume while evaluating <=25% of the
space.
"""

import dataclasses
import json
import math

import numpy as np
import pytest

from repro.core import (ArchRequest, ResourceBudget, SLA, bind,
                        compressed_protocol, pareto_front, run_dse)
from repro.core.pareto import hypervolume_2d
from repro.core.search import (NSGA2Search, SearchDriver, SearchSpec,
                               constrained_non_dominated_sort,
                               crowding_distance, evaluate_space,
                               load_search_state, run_search,
                               save_search_state)
from repro.sim.resources import ALVEO_U45N
from repro.sim.switch_problem import SwitchDSEProblem
from repro.traces import hft

BOUND = bind(compressed_protocol(addr_bits=4, length_bits=6), flit_bits=256)
SLA_HFT = SLA(p99_latency_ns=5000, drop_rate=1e-3)
BUDGET = ResourceBudget(dict(ALVEO_U45N))


def _problem(duration_s=8e-5, **kw):
    return SwitchDSEProblem(ArchRequest(n_ports=8, addr_bits=4), BOUND,
                            hft(seed=0, duration_s=duration_s),
                            back_annotation=False, **kw)


def _shorts(valid):
    return [c.short() for c, _ in valid]


# --------------------------------------------------------------------------
# design space
# --------------------------------------------------------------------------

def test_switch_space_is_parameterized_and_large():
    space = _problem().space()
    assert space.size() >= 1024                 # the enlarged joint space
    assert set(space.signature()) == {
        "bus_bits", "fwd", "voq", "sched", "islip_iters", "hash_banks",
        "hash_depth"}
    # explicit request policies collapse to single-choice dimensions
    from repro.core.archspec import SchedulerKind
    prob = SwitchDSEProblem(
        ArchRequest(n_ports=8, addr_bits=4, bus_bits=256,
                    sched=SchedulerKind.RR),
        BOUND, hft(seed=0, duration_s=8e-5), back_annotation=False)
    sig = prob.space().signature()
    assert sig["bus_bits"] == 1 and sig["sched"] == 1


def test_comm_space_and_decode():
    """The comm problem's space/decode are pure: no fabric build needed."""
    from repro.comm.dse_comm import CommDSEProblem, CommSpec
    space = CommDSEProblem.space(None)
    assert space.size() == 24
    assert set(space.signature()) == {"payload", "a2a_chunks", "microbatches"}
    c = CommDSEProblem.decode(None, space.assignment((1, 2, 0)))
    assert c == CommSpec(capacity_factor=2.0, payload="int8", a2a_chunks=4,
                         microbatches=1)


def test_decode_canonicalises_inert_genes():
    prob = _problem()
    space = prob.space()
    names = [d.name for d in space.dims]
    base = {d.name: d.choices[0] for d in space.dims}
    from repro.core.archspec import ForwardTableKind, SchedulerKind
    base["sched"] = SchedulerKind.RR
    base["fwd"] = ForwardTableKind.FULL_LOOKUP
    a = prob.decode({**base, "islip_iters": 1, "hash_banks": 2,
                     "hash_depth": 128})
    b = prob.decode({**base, "islip_iters": 4, "hash_banks": 8,
                     "hash_depth": 512})
    assert a == b                               # inert genes -> one phenotype
    assert names == list(space.signature())


# --------------------------------------------------------------------------
# NSGA-II primitives (example-based twins of the hypothesis properties)
# --------------------------------------------------------------------------

def test_constrained_sort_feasible_dominates_infeasible():
    objs = np.array([[1.0, 1.0], [0.0, 0.0], [2.0, 2.0]])
    viol = np.array([0.0, 5.0, 0.0])
    ranks = constrained_non_dominated_sort(objs, viol)
    assert ranks[0] == 0                        # feasible front
    assert ranks[2] == 1                        # dominated feasible
    assert ranks[1] == 2                        # infeasible ranks last
    # two infeasible points order by violation alone
    ranks2 = constrained_non_dominated_sort(
        np.array([[0.0, 0.0], [9.0, 9.0]]), np.array([2.0, 1.0]))
    assert ranks2[1] < ranks2[0]


def test_crowding_distance_boundaries_infinite():
    objs = np.array([[0.0, 3.0], [1.0, 2.0], [2.0, 1.0], [3.0, 0.0]])
    d = crowding_distance(objs)
    assert math.isinf(d[0]) and math.isinf(d[3])
    assert np.all(np.isfinite(d[1:3]))


def test_plateau_clock_waits_for_first_feasible_point():
    """An all-infeasible run must exhaust its generations, not stop after
    ``patience`` 0->0 "plateaus" while still hunting feasibility."""
    from repro.core.search import DesignSpace, Dim
    space = DesignSpace((Dim("x", tuple(range(8))), Dim("y", tuple(range(8)))))
    eng = NSGA2Search(space, SearchSpec(population=8, generations=6, seed=0,
                                        patience=2))
    while not eng.done:
        asked = eng.ask()
        eng.tell({g: ((float(g[0]), float(g[1])), 1.0 + g[0]) for g in asked})
    assert eng.generation == 6                  # ran the full budget
    assert eng.archive() == [] and eng.hv_history == [0.0] * 6


def test_engine_same_seed_bit_identical_and_hv_monotone():
    """Engine-level twin of the hypothesis NSGA-II invariants, on a cheap
    synthetic objective (no problem, no surrogate)."""
    from repro.core.search import DesignSpace, Dim
    space = DesignSpace(tuple(
        Dim(f"x{i}", tuple(range(8))) for i in range(4)))

    def objective(g):
        f1 = float(sum(g))
        f2 = float(sum((7 - x) ** 2 for x in g))
        return (f1, f2)

    def drive(seed):
        eng = NSGA2Search(space, SearchSpec(population=16, generations=8,
                                            seed=seed, patience=100))
        while not eng.done:
            asked = eng.ask()
            eng.tell({g: (objective(g), 0.0) for g in asked})
        return eng

    a, b = drive(11), drive(11)
    assert a.front() == b.front()               # bit-identical
    assert a.hv_history == b.hv_history
    hist = a.hv_history
    assert all(h2 >= h1 - 1e-12 for h1, h2 in zip(hist, hist[1:]))
    c = drive(12)
    assert c.hv_history[-1] > 0.0               # different seed still works


# --------------------------------------------------------------------------
# determinism matrix
# --------------------------------------------------------------------------

def test_same_seed_identical_dse_result():
    spec = SearchSpec(population=16, generations=5, seed=3)
    res1 = run_dse(_problem(), SLA_HFT, BUDGET, search=spec, top_k=4)
    res2 = run_dse(_problem(), SLA_HFT, BUDGET, search=spec, top_k=4)
    assert res1.best == res2.best
    assert [a.short() for a, _ in res1.pareto] == [a.short() for a, _ in res2.pareto]
    assert [(v.p99_latency_ns, v.drop_rate) for _, v, _, _ in res1.evaluated] \
        == [(v.p99_latency_ns, v.drop_rate) for _, v, _, _ in res2.evaluated]
    assert [(lg.stage, lg.considered, lg.survived, tuple(lg.notes))
            for lg in res1.logs] \
        == [(lg.stage, lg.considered, lg.survived, tuple(lg.notes))
            for lg in res2.logs]


def test_identical_across_verify_engines():
    """netsim vs auto: escalation annotates meta, never the ranking."""
    spec = SearchSpec(population=16, generations=4, seed=2)
    res_n = run_dse(_problem(verify_engine="netsim"), SLA_HFT, BUDGET,
                    search=spec, top_k=3)
    res_a = run_dse(_problem(verify_engine="auto"), SLA_HFT, BUDGET,
                    search=spec, top_k=3)
    assert res_n.best == res_a.best
    assert [a.short() for a, _ in res_n.pareto] \
        == [a.short() for a, _ in res_a.pareto]
    assert res_a.best_verify.meta.get("escalated") is not None
    assert res_n.best_verify.meta.get("escalated") is None


def test_resume_matches_uninterrupted(tmp_path):
    spec = SearchSpec(population=16, generations=6, seed=4, patience=100)
    prob = _problem()
    full = run_search(prob, spec, SLA_HFT)
    ck = str(tmp_path / "ck")
    part = run_search(_problem(), spec, SLA_HFT, checkpoint_dir=ck,
                      max_generations_this_run=2)
    assert part.generations == 2                # genuinely interrupted
    resumed = run_search(_problem(), spec, SLA_HFT, checkpoint_dir=ck,
                         resume=True)
    assert resumed.resumed
    assert resumed.generations == full.generations
    assert _shorts(resumed.valid) == _shorts(full.valid)
    assert resumed.hv_history == full.hv_history
    # and the full DSE result is identical either way
    res_full = run_dse(_problem(), SLA_HFT, BUDGET, search=spec, top_k=3)
    res_res = run_dse(_problem(), SLA_HFT, BUDGET, search=spec, top_k=3,
                      checkpoint_dir=ck, resume=True)
    assert res_full.best == res_res.best
    assert [a.short() for a, _ in res_full.pareto] \
        == [a.short() for a, _ in res_res.pareto]


def test_checkpoint_roundtrip_restores_rng_state_exactly(tmp_path):
    spec = SearchSpec(population=12, generations=5, seed=8, patience=100)
    prob = _problem()
    driver = SearchDriver(prob, spec, SLA_HFT)
    for _ in range(2):
        driver.tell_candidates(prob.surrogate_batch(driver.ask_candidates()))
    ck = str(tmp_path / "ck")
    save_search_state(ck, driver.engine)
    eng = load_search_state(ck, prob.space(), spec)
    assert eng.rng.bit_generator.state == driver.engine.rng.bit_generator.state
    # the next draws are bit-identical too
    assert eng.rng.integers(1 << 30, size=8).tolist() \
        == driver.engine.rng.integers(1 << 30, size=8).tolist()
    # full engine state round-trips
    assert eng.parents == driver.engine.parents
    assert eng.pending == driver.engine.pending
    assert eng.cache == driver.engine.cache
    assert eng.hv_history == driver.engine.hv_history
    assert eng.ref == driver.engine.ref


def test_resume_warns_when_checkpoint_dir_is_empty(tmp_path):
    """A mistyped --checkpoint-dir must not silently restart from gen 0."""
    spec = SearchSpec(population=8, generations=1, seed=0)
    with pytest.warns(RuntimeWarning, match="no search checkpoint"):
        out = run_search(_problem(), spec, SLA_HFT,
                         checkpoint_dir=str(tmp_path / "nope"), resume=True)
    assert not out.resumed


def test_resume_validates_spec_and_space(tmp_path):
    spec = SearchSpec(population=12, generations=4, seed=1)
    prob = _problem()
    ck = str(tmp_path / "ck")
    run_search(prob, spec, SLA_HFT, checkpoint_dir=ck,
               max_generations_this_run=1)
    with pytest.raises(ValueError, match="SearchSpec differs"):
        load_search_state(ck, prob.space(),
                          dataclasses.replace(spec, seed=2))
    other = SwitchDSEProblem(
        ArchRequest(n_ports=8, addr_bits=4, bus_bits=256), BOUND,
        hft(seed=0, duration_s=8e-5), back_annotation=False)
    with pytest.raises(ValueError, match="design space differs"):
        load_search_state(ck, other.space(), spec)


# --------------------------------------------------------------------------
# acceptance: search quality vs exhaustive on the enlarged hft space
# --------------------------------------------------------------------------

def test_nsga2_hits_exhaustive_hypervolume_within_budget():
    """>=95% of the exhaustive front's hypervolume with <=25% of the space
    evaluated (the ISSUE 4 acceptance bar, also reported by
    ``benchmarks/search_quality.py`` into BENCH_dse.json)."""
    prob = _problem(duration_s=4e-4)            # the full Table-II hft trace
    space = prob.space()
    assert space.size() >= 1024
    ex = evaluate_space(prob, SLA_HFT)
    ref = tuple(float(x) for x in ex.objectives.max(axis=0) * 1.1 + 1e-9)
    hv_ex = hypervolume_2d(ex.front_objectives(), ref)
    assert hv_ex > 0

    budget = space.size() // 4
    spec = SearchSpec(population=48, generations=10, seed=0,
                      max_evaluations=budget)
    out = run_search(prob, spec, SLA_HFT)
    assert out.evaluations <= budget
    assert out.surrogate_rows <= budget
    objs = np.asarray([prob.surrogate_objectives(c, sr)
                       for c, sr in out.valid], float)
    keep = pareto_front(list(range(len(objs))), key=lambda i: tuple(objs[i]))
    hv_s = hypervolume_2d(objs[keep], ref)
    assert hv_s >= 0.95 * hv_ex, (
        f"NSGA-II reached {hv_s / hv_ex:.3f} of exhaustive hypervolume "
        f"({out.surrogate_rows}/{space.size()} evaluations)")


# --------------------------------------------------------------------------
# API + CLI surface
# --------------------------------------------------------------------------

def test_scenario_search_roundtrip_bit_for_bit():
    from repro.api import Scenario, registry
    s = registry["hft"].override(
        search=SearchSpec(population=20, generations=6, seed=5,
                          max_evaluations=200, checkpoint_dir="ckpt/hft"))
    assert Scenario.from_json(s.to_json()) == s
    d = json.loads(s.to_json())
    assert d["search"]["algorithm"] == "nsga2"
    assert d["search"]["max_evaluations"] == 200
    # dropping the search key round-trips to exhaustive mode
    assert Scenario.from_dict(registry["hft"].to_dict()).search is None


def test_campaign_locksteps_search_scenarios_with_solo_parity():
    from repro.api import registry, run_campaign, run_scenario
    spec = SearchSpec(population=12, generations=3, seed=5)
    base = registry["hft"].override(back_annotation=False, top_k=2,
                                    trace_params={"duration_s": 8e-5},
                                    search=spec)
    relaxed = base.override(name="hft_relaxed", sla_p99_latency_ns=1e6,
                            search=SearchSpec(population=12, generations=3,
                                              seed=6))
    campaign = run_campaign([base, relaxed], name="lockstep")
    assert campaign.shared_trace_scenarios == 1
    # generational lockstep: one batched call per generation, both engines
    assert campaign.stage2_batches <= spec.generations
    for s in (base, relaxed):
        solo = run_scenario(s)
        batched = campaign[s.name]
        assert batched.best == solo.best
        assert [a.short() for a, _ in batched.pareto] \
            == [a.short() for a, _ in solo.pareto]


def test_cli_search_run_and_resume(tmp_path, capsys):
    from repro.api.cli import main
    ck = str(tmp_path / "ck")
    args = ["run", "hft", "--duration-s", "8e-05", "--no-back-annotation",
            "--top-k", "2", "--search", "nsga2", "--generations", "3",
            "--population", "8", "--search-seed", "1",
            "--checkpoint-dir", ck,
            "--out", str(tmp_path / "report.json")]
    assert main(args) == 0
    first = capsys.readouterr().out
    assert "search-nsga2" in first
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["scenario"]["search"]["seed"] == 1
    assert any(st["stage"] == "search-nsga2" for st in report["stages"])
    # resuming from the finished checkpoint reproduces the identical result
    assert main(args + ["--resume"]) == 0
    second = capsys.readouterr().out
    assert first.splitlines()[1:] == second.splitlines()[1:]


def test_cli_search_flags_require_search():
    from repro.api.cli import main
    with pytest.raises(SystemExit, match="--search"):
        main(["run", "hft", "--generations", "3"])


def test_error_messages_name_problem_and_shapes():
    from repro.core.dse import stage2_screen, stage4_verify

    class Broken(SwitchDSEProblem):
        def surrogate_batch(self, archs):
            return super().surrogate_batch(archs)[:-1]

        def verify_batch(self, archs):
            return super().verify_batch(archs)[:-1]

    prob = Broken(ArchRequest(n_ports=8, addr_bits=4), BOUND,
                  hft(seed=0, duration_s=8e-5), back_annotation=False)
    cands = prob.candidates()[:4]
    with pytest.raises(ValueError, match=r"Broken\.surrogate_batch.*\[3\].*\[4\]"):
        stage2_screen(prob, cands, SLA_HFT)
    sized = [(c, prob.resources(c)) for c in cands]
    with pytest.raises(ValueError, match=r"Broken\.verify_batch.*\[3\].*\[4\]"):
        stage4_verify(prob, sized, SLA_HFT)


def test_search_meets_only_bucket_widths(monkeypatch):
    """A short NSGA-II exploration asks stage 2 and stage 4 for many widths;
    every device call pads them to ``bucket_size``, so the stage-2 and
    stage-4 programs see at most the five widths up to 48."""
    import repro.kernels.netsim.ops as ops
    import repro.sim.batched_surrogate as bs
    from repro.api import registry, run_scenario
    from repro.launch.mesh import bucket_size

    seen = {"stage2": [], "stage4": []}

    def spy(fn, stage, axis):
        def call(*args, **kw):
            seen[stage].append(args[3].shape[axis])
            return fn(*args, **kw)
        return call

    monkeypatch.setattr(bs, "_engine", spy(bs._engine, "stage2", 0))
    monkeypatch.setattr(ops, "_round1", spy(ops._round1, "stage4", 1))
    monkeypatch.setattr(ops, "_gated_replay",
                        spy(ops._gated_replay, "stage4", 0))
    s = registry["hft"].override(
        back_annotation=False, top_k=8, trace_params={"duration_s": 8e-5},
        search=SearchSpec(population=24, generations=4, seed=3))
    report = run_scenario(s)
    assert report.stage4_candidates > 0
    allowed = {bucket_size(n) for n in range(1, 49)}
    assert allowed == {8, 16, 24, 32, 48}
    for stage, widths in seen.items():
        assert widths and set(widths) <= allowed, (stage, widths)
