"""Scenario/Campaign API: serialization, registry, runner parity, CLI."""

import dataclasses
import json
import math

import numpy as np
import pytest

from repro.api import (ProtocolSpec, Scenario, ScenarioRegistry, TraceSpec,
                       registry, run_campaign, run_scenario)
from repro.api.cli import load_campaign_config, main, resolve_entry
from repro.api.runner import build_bound
from repro.api.scenario import CommModelSpec, Fidelity, SearchSpec
from repro.core import (AUTO, ArchRequest, ResourceBudget, SLA, SchedulerKind,
                        VOQKind, Field, bind, compressed_protocol)
from repro.sim import optimize_switch
from repro.traces import Trace, WORKLOADS, hft


def _roundtrip(s: Scenario) -> Scenario:
    return Scenario.from_dict(json.loads(json.dumps(s.to_dict())))


# ------------------------------------------------------------- serialization

def test_registry_scenarios_roundtrip_bit_for_bit():
    for s in registry:
        assert _roundtrip(s) == s


def test_roundtrip_inline_protocol_auto_policies_and_inf():
    proto = compressed_protocol(name="wire", addr_bits=5, qos_bits=3,
                                length_bits=7, seq_bits=4)
    s = Scenario(
        name="inline_test",
        protocol=ProtocolSpec.inline(proto),
        flit_bits=128,
        binding={"opcode": "qos"},
        trace=TraceSpec(generator="uniform",
                        params={"seed": 3, "duration_s": 1e-4, "load": 0.5}),
        arch=ArchRequest(n_ports=8, addr_bits=5, bus_bits=AUTO, voq=AUTO,
                         sched=SchedulerKind.RR, voq_depth=32),
        sla=SLA(p99_latency_ns=math.inf, drop_rate=5e-3),
        budget=ResourceBudget({"luts": 1e6, "brams": math.inf}),
        fidelity=Fidelity(back_annotation=False, top_k=3),
        notes="inline + AUTO + inf round-trip",
    )
    s2 = _roundtrip(s)
    assert s2 == s
    # AUTO must come back as the singleton, not a lookalike
    assert s2.arch.bus_bits is AUTO and s2.arch.voq is AUTO
    assert s2.arch.sched is SchedulerKind.RR
    assert math.isinf(s2.sla.p99_latency_ns)
    assert s2.protocol.fields == tuple(proto.fields)
    # the rebuilt protocol is layout-identical
    assert s2.protocol.build().compile(128) == proto.compile(128)


def test_roundtrip_comm_scenario_and_file_trace(tmp_path):
    s = registry["moe_dispatch"]
    assert _roundtrip(s) == s
    # file-sourced trace spec
    p = tmp_path / "t.npz"
    hft(seed=1, duration_s=5e-5).save(p)
    s = dataclasses.replace(registry["hft"], trace=TraceSpec(path=str(p)))
    s2 = _roundtrip(s)
    assert s2 == s
    tr = s2.trace.build()
    assert tr.name == "hft" and len(tr) == len(hft(seed=1, duration_s=5e-5))


def test_scenario_json_file_and_validation(tmp_path):
    s = registry["underwater"]
    path = tmp_path / "s.json"
    s.save(path)
    assert Scenario.load(path) == s
    with pytest.raises(ValueError):
        Scenario(name="bad", domain="switch", arch=None)
    with pytest.raises(ValueError):
        Scenario(name="bad", domain="nope",
                 arch=ArchRequest(n_ports=8, addr_bits=4))
    with pytest.raises(ValueError):
        TraceSpec(generator="hft", path="x.npz")
    with pytest.raises(ValueError):
        ProtocolSpec(builder="definitely_not_a_builder")


def test_override_surface():
    s = registry["hft"].override(sla_p99_latency_ns=123.0,
                                 trace_params={"duration_s": 1e-4},
                                 back_annotation=False, top_k=2)
    assert s.sla.p99_latency_ns == 123.0
    assert s.sla.drop_rate == registry["hft"].sla.drop_rate
    assert s.trace.params["duration_s"] == 1e-4
    assert s.trace.params["seed"] == 0          # merged, not replaced
    assert s.fidelity == Fidelity(back_annotation=False, top_k=2)
    # the original is untouched (frozen specs)
    assert registry["hft"].fidelity.back_annotation is True


# ------------------------------------------------------------------ registry

def test_registry_covers_all_trace_workloads():
    switch_gens = {s.trace.generator for s in registry if s.domain == "switch"}
    assert set(WORKLOADS) <= switch_gens
    # and each workload scenario is named after its generator
    for name in WORKLOADS:
        assert registry[name].trace.generator == name


def test_registry_has_comm_scenarios():
    comm = [s for s in registry if s.domain == "comm"]
    assert {s.name for s in comm} >= {"moe_dispatch", "grad_bucket"}
    for s in comm:
        assert isinstance(s.comm, CommModelSpec)


def test_registry_duplicate_and_lookup():
    r = ScenarioRegistry()
    s = registry["hft"]
    r.register(s)
    with pytest.raises(ValueError):
        r.register(s)
    r.register(s.override(name="hft"), replace=True)
    assert "hft" in r and r["hft"].name == "hft"
    with pytest.raises(KeyError):
        r["nope"]


# ------------------------------------------------------------- traces on disk

def test_trace_save_load_roundtrip(tmp_path):
    tr = hft(seed=2, duration_s=1e-4)
    p = tmp_path / "trace.npz"
    tr.save(p)
    tr2 = Trace.load(p)
    assert tr2.name == tr.name
    assert tr2.n_ports == tr.n_ports and tr2.link_gbps == tr.link_gbps
    np.testing.assert_array_equal(tr2.time_s, tr.time_s)
    np.testing.assert_array_equal(tr2.src, tr.src)
    np.testing.assert_array_equal(tr2.dst, tr.dst)
    np.testing.assert_array_equal(tr2.payload_bytes, tr.payload_bytes)
    assert tr2.src.dtype == tr.src.dtype
    assert tr2.payload_bytes.dtype == tr.payload_bytes.dtype


# -------------------------------------------------------------------- runner

def test_run_scenario_matches_legacy_optimize_switch():
    """Acceptance: identical Pareto front + best arch vs the legacy path."""
    scenario = registry["hft"].override(back_annotation=False)
    report = run_scenario(scenario)

    bound = build_bound(scenario)
    res, _ = optimize_switch(scenario.arch, bound, scenario.trace.build(),
                             sla=scenario.sla, back_annotation=False)
    assert report.best == res.best
    assert report.best_verify.p99_latency_ns == res.best_verify.p99_latency_ns
    assert report.best_verify.drop_rate == res.best_verify.drop_rate
    assert ([a.short() for a, _ in report.pareto]
            == [a.short() for a, _ in res.pareto])
    assert [(lg.stage, lg.considered, lg.survived) for lg in report.result.logs] \
        == [(lg.stage, lg.considered, lg.survived) for lg in res.logs]
    # the structured report serializes
    d = json.loads(json.dumps(report.to_dict()))
    assert d["best"] == res.best.short()
    assert d["stages"][0]["stage"] == "stage1-static"
    assert report.resources["brams"] > 0


def _tiny(name, **trace_params):
    return registry[name].override(back_annotation=False, top_k=2,
                                   trace_params=trace_params)


def test_run_campaign_parity_and_aggregate_throughput():
    """Campaign over 3 registry scenarios: per-scenario results identical to
    run_scenario, aggregate batched stage-2 throughput reported."""
    scns = [_tiny("hft", duration_s=8e-5),
            _tiny("underwater", duration_s=4e-4),
            _tiny("industry", duration_s=4e-4)]
    campaign = run_campaign(scns, name="smoke")
    assert len(campaign.reports) == 3
    assert campaign.stage2_candidates >= sum(
        r.result.logs[0].survived for r in campaign.reports)
    assert campaign.stage2_batches == 3          # three distinct traces
    assert campaign.stage2_cands_per_sec > 0
    for s in scns:
        solo = run_scenario(s)
        batched = campaign[s.name]
        assert batched.best == solo.best
        assert ([a.short() for a, _ in batched.pareto]
                == [a.short() for a, _ in solo.pareto])
    # report is JSON-serializable
    json.dumps(campaign.to_dict())


def test_run_campaign_shares_traces_and_batches_across_scenarios():
    """Two scenarios over the same trace+protocol share one batched call."""
    base = _tiny("hft", duration_s=8e-5)
    relaxed = base.override(name="hft_relaxed", sla_p99_latency_ns=1e6)
    campaign = run_campaign([base, relaxed], name="shared")
    assert campaign.shared_trace_scenarios == 1
    assert campaign.stage2_batches == 1          # one call, both scenarios
    assert campaign["hft"].best is not None
    # same spec -> same best either way; the relaxed SLA can only widen
    solo = run_scenario(base)
    assert campaign["hft"].best == solo.best


@pytest.mark.parametrize("search", [
    None, SearchSpec(population=8, generations=2, seed=3)],
    ids=["exhaustive", "nsga2"])
def test_every_driver_gives_one_result(search):
    """run_dse, run_scenario, a one-scenario campaign and the serve engine
    are clients of one state machine: same stage logs, front and champion."""
    from repro.api import ScenarioReport
    from repro.api.runner import build_problem
    from repro.api.service import DSEServeEngine
    from repro.core import run_dse

    s = dataclasses.replace(_tiny("hft", duration_s=8e-5), search=search)
    problem, sla, budget = build_problem(s)
    by_dse = run_dse(problem, sla, budget, delta=s.fidelity.delta,
                     top_k=s.fidelity.top_k, search=search)
    eng = DSEServeEngine(slots=1)
    req = eng.submit(s)
    eng.run_until_drained()
    got = {
        "run_dse": ScenarioReport(s, by_dse, problem, 0.0).to_dict(),
        "run_scenario": run_scenario(s).to_dict(),
        "run_campaign": run_campaign([s]).reports[0].to_dict(),
        "serve": req.report,
    }
    want = {k: got["run_scenario"][k]
            for k in ("stages", "pareto", "best", "best_verify")}
    assert want["best"] is not None and want["pareto"]
    for driver, d in got.items():
        assert {k: d[k] for k in want} == want, driver


# ----------------------------------------------------------------------- CLI

def test_cli_list_and_show(capsys):
    assert main(["list"]) == 0
    out = capsys.readouterr().out
    for name in registry.names():
        assert name in out
    assert main(["show", "hft"]) == 0
    spec = json.loads(capsys.readouterr().out)
    assert Scenario.from_dict(spec) == registry["hft"]


def test_cli_run_with_overrides_and_report(tmp_path, capsys):
    out_file = tmp_path / "report.json"
    cfg_file = tmp_path / "scenario.json"
    rc = main(["run", "hft", "--duration-s", "8e-05", "--no-back-annotation",
               "--top-k", "2", "--out", str(out_file),
               "--save-config", str(cfg_file)])
    assert rc == 0
    capsys.readouterr()
    report = json.loads(out_file.read_text())
    assert report["best"] is not None
    assert report["scenario"]["fidelity"]["back_annotation"] is False
    # the saved config re-runs identically through the file path
    rc = main(["run", str(cfg_file), "--top-k", "2"])
    assert rc == 0


def test_cli_sweep_config_file(tmp_path, capsys):
    cfg = {
        "name": "smoke",
        "scenarios": [
            {"base": "hft",
             "trace": {"params": {"duration_s": 8e-5}},
             "fidelity": {"back_annotation": False, "top_k": 2}},
            {"base": "underwater",
             "trace": {"params": {"duration_s": 4e-4}},
             "fidelity": {"back_annotation": False, "top_k": 2}},
        ],
    }
    path = tmp_path / "campaign.json"
    path.write_text(json.dumps(cfg))
    out_file = tmp_path / "campaign_report.json"
    assert main(["sweep", "--config", str(path), "--out", str(out_file)]) == 0
    rep = json.loads(out_file.read_text())
    assert rep["name"] == "smoke"
    assert len(rep["scenarios"]) == 2
    assert rep["stage2_cands_per_sec"] > 0


def test_campaign_config_resolution():
    cfg = load_campaign_config(["hft", {"base": "underwater",
                                        "sla": {"p99_latency_ns": 1234.0}}])
    assert cfg["name"] == "campaign"
    assert cfg["scenarios"][0] == registry["hft"]
    over = cfg["scenarios"][1]
    assert over.sla.p99_latency_ns == 1234.0
    # deep-merge keeps untouched leaves
    assert over.sla.drop_rate == registry["underwater"].sla.drop_rate
    assert over.trace == registry["underwater"].trace
    full = resolve_entry(registry["hft"].to_dict())
    assert full == registry["hft"]


def test_campaign_override_can_switch_trace_source(tmp_path):
    """A base+override entry may swap a generator trace for a saved file."""
    p = tmp_path / "cap.npz"
    hft(seed=4, duration_s=5e-5).save(p)
    s = resolve_entry({"base": "hft", "trace": {"path": str(p)}})
    assert s.trace == TraceSpec(path=str(p))
    assert len(s.trace.build()) == len(hft(seed=4, duration_s=5e-5))
    # and a generator swap drops the base generator's params wholesale
    s = resolve_entry({"base": "hft", "trace": {"generator": "uniform"}})
    assert s.trace == TraceSpec(generator="uniform")
    # param-only overrides still deep-merge into the base trace
    s = resolve_entry({"base": "hft", "trace": {"params": {"duration_s": 1e-4}}})
    assert s.trace.generator == "hft" and s.trace.params["seed"] == 0
    assert s.trace.params["duration_s"] == 1e-4
