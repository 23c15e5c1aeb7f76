"""Spans and counters (``repro.analysis.spans``): off costs nothing visible,
on records the layer tree of an exploration, a search and a served request.

Recording is on exactly while a ``jax.profiler`` session runs, so every
recorded case here runs inside ``jax.profiler.trace`` on the CPU.
"""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import spans
from repro.analysis.spans import note, span
from repro.api import registry, run_scenario
from repro.api.golden import diff_reports
from repro.api.scenario import SearchSpec
from repro.api.service import DSEServeEngine

#: an exhaustive exploration of the 32-port datacenter switch on a short trace
DC32 = registry["datacenter"].override(
    back_annotation=False, delta=2.5, trace_params={"duration_s": 3e-4})

EXPLORE_TREE = {
    "spac.explore": None,
    "spac.build": "spac.explore",
    "spac.stage1": "spac.explore",
    "spac.stage2": "spac.explore",
    "spac.stage2.timeline": "spac.stage2",
    "spac.stage2.prepare": "spac.stage2",
    "spac.stage2.scan": "spac.stage2",
    "spac.stage2.reduce": "spac.stage2",
    "spac.stage2.occupancy": "spac.stage2.reduce",
    "spac.screen": "spac.explore",
    "spac.stage3": "spac.explore",
    "spac.stage4": "spac.explore",
    "spac.stage4.timeline": "spac.stage4",
    "spac.stage4.prepare": "spac.stage4",
    "spac.stage4.round1": "spac.stage4",
    "spac.stage4.reduce": "spac.stage4",
    "spac.finalize": "spac.explore",
}


def _recorded(logdir, fn):
    """``fn()`` under a CPU profiler session; (result, records)."""
    spans.clear()
    with jax.profiler.trace(str(logdir)):
        out = fn()
    return out, spans.records()


def _one(recs, name):
    found = [r for r in recs if r.name == name]
    assert len(found) == 1, (name, [r.name for r in recs])
    return found[0]


@pytest.fixture(scope="module")
def explored(tmp_path_factory):
    run_scenario(DC32)                     # compile outside the traced run
    logdir = tmp_path_factory.mktemp("profile")
    report, recs = _recorded(logdir, lambda: run_scenario(DC32))
    return report, recs, str(logdir)


def test_off_records_nothing_and_still_times():
    spans.clear()
    report = run_scenario(DC32)
    assert spans.records() == [] and spans.dropped() == 0
    assert report.stage2_time_s > 0 and report.stage4_time_s > 0
    assert report.wall_time_s >= report.stage2_time_s + report.stage4_time_s


@pytest.mark.parametrize("name", sorted(EXPLORE_TREE))
def test_exploration_records_the_span_tree(explored, name):
    _, recs, _ = explored
    by_id = {r.id: r for r in recs}
    rec = _one(recs, name)
    want = EXPLORE_TREE[name]
    got = by_id[rec.parent].name if rec.parent is not None else None
    assert got == want


def test_children_nest_inside_parents_under_one_root(explored):
    _, recs, _ = explored
    by_id = {r.id: r for r in recs}
    root = _one(recs, "spac.explore")
    assert root.attrs["scenario"] == "datacenter"
    for r in recs:
        assert r.root == root.id
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns


def test_time_fields_are_the_spans_durations(explored):
    report, recs, _ = explored
    assert report.stage2_time_s == _one(recs, "spac.stage2").seconds
    assert report.stage4_time_s == _one(recs, "spac.stage4").seconds
    assert report.wall_time_s <= _one(recs, "spac.explore").seconds


def test_stage_counters(explored):
    report, recs, _ = explored
    s2, s4 = _one(recs, "spac.stage2"), _one(recs, "spac.stage4")
    active = report.result.logs[0].survived
    assert s2.attrs["rows"] * s2.attrs["events"] == active * len(report.problem.trace)
    assert _one(recs, "spac.build").attrs["events"] == len(report.problem.trace)
    assert _one(recs, "spac.stage1").attrs["survivors"] == active
    assert s4.attrs["rows"] == report.stage4_candidates
    assert 1 <= s4.attrs["unique_rows"] <= s4.attrs["rows"]
    assert s4.attrs["rounds"] >= 1
    assert s4.attrs["fallback_rows"] == 0


@pytest.mark.parametrize("name", ["spac.stage2.scan", "spac.stage4.round1"])
def test_device_calls_note_their_sweeps(explored, name):
    from repro.kernels.xbar import SWEEP_CAP

    rec = _one(explored[1], name)
    assert 1 <= rec.attrs["sweeps"] < SWEEP_CAP
    assert rec.attrs["scan_fallback"] == 0


@pytest.mark.parametrize("name,parent", [
    ("spac.stage2.scan", "spac.stage2"), ("spac.stage4.round1", "spac.stage4")])
def test_device_calls_note_their_pad_rows(explored, name, parent):
    from repro.launch.mesh import bucket_size

    report, recs, _ = explored
    rec = _one(recs, name)
    rows = (_one(recs, parent).attrs["unique_rows"] if parent == "spac.stage4"
            else _one(recs, parent).attrs["rows"])
    assert rec.attrs["pad_rows"] == bucket_size(rows) - rows


def test_occupancy_span_counts_rows_and_events(explored):
    report, recs, _ = explored
    occ, s2 = _one(recs, "spac.stage2.occupancy"), _one(recs, "spac.stage2")
    assert occ.attrs == {"rows": s2.attrs["rows"],
                         "events": len(report.problem.trace)}


def test_replay_notes_pad_rows(tmp_path):
    """Later fixed-point rounds replay only the dropping rows, padded."""
    from repro.core import (ArchRequest, bind, compressed_protocol,
                            enumerate_candidates)
    from repro.sim import run_netsim_batched
    from repro.traces import hft

    bound = bind(compressed_protocol(addr_bits=4, length_bits=6),
                 flit_bits=256)
    cands = [a.with_depth(2) for a in
             enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:3]]
    vs, recs = _recorded(tmp_path, lambda: run_netsim_batched(
        cands, bound, hft(seed=0), back_annotation=False, use_kernel=True))
    assert any(v.drop_rate > 0 for v in vs)
    replays = [r for r in recs if r.name == "spac.stage4.replay"]
    # each round replays 1..3 dropping rows, padded to the bucket of 8
    assert replays and all(r.attrs["pad_rows"] in {5, 6, 7} for r in replays)


def test_profiler_host_plane_holds_the_spans(explored):
    _, recs, logdir = explored
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {r.name for r in recs} <= names


def test_report_identical_with_recording_on_and_off(explored):
    report, _, _ = explored
    assert diff_reports(run_scenario(DC32).to_dict(), report.to_dict()) == []


def test_search_nests_ask_tell_and_evaluation(tmp_path):
    s = registry["hft"].override(
        back_annotation=False, top_k=2, trace_params={"duration_s": 8e-5},
        search=SearchSpec(population=8, generations=2, seed=3))
    run_scenario(s)
    report, recs = _recorded(tmp_path, lambda: run_scenario(s))
    by_id = {r.id: r for r in recs}
    search = _one(recs, "spac.search")
    assert report.stage2_time_s == search.seconds
    for name in ("spac.search.ask", "spac.search.tell", "spac.stage2"):
        found = [r for r in recs if r.name == name]
        assert found and all(by_id[r.parent] is search for r in found)
    asks = [r for r in recs if r.name == "spac.search.ask"]
    assert [r.attrs["generation"] for r in asks] == list(range(len(asks)))


#: the same tree under NSGA-II: the search replaces stage 1, the stage-2
#: call and the screen (``None``: not recorded at all)
SEARCH_TREE = {
    "spac.build": "spac.explore",
    "spac.search": "spac.explore",
    "spac.stage1": None,
    "spac.screen": None,
    "spac.stage3": "spac.explore",
    "spac.stage4": "spac.explore",
    "spac.finalize": "spac.explore",
}


@pytest.fixture(scope="module")
def searched(tmp_path_factory):
    s = registry["hft"].override(
        back_annotation=False, top_k=2, trace_params={"duration_s": 8e-5},
        search=SearchSpec(population=8, generations=2, seed=3))
    run_scenario(s)                        # compile outside the traced run
    return _recorded(tmp_path_factory.mktemp("search"),
                     lambda: run_scenario(s))


@pytest.mark.parametrize("name", sorted(SEARCH_TREE))
def test_search_exploration_records_the_span_tree(searched, name):
    _, recs = searched
    want = SEARCH_TREE[name]
    if want is None:
        assert [r for r in recs if r.name == name] == []
        return
    by_id = {r.id: r for r in recs}
    rec = _one(recs, name)
    assert by_id[rec.parent].name == want
    search = _one(recs, "spac.search")
    if rec is not search:              # no host stage runs inside the search
        assert rec.end_ns <= search.start_ns or rec.start_ns >= search.end_ns


def test_search_notes_generations_and_evaluated_rows(tmp_path):
    s = registry["hft"].override(
        back_annotation=False, top_k=2, trace_params={"duration_s": 8e-5},
        search=SearchSpec(population=8, generations=3, seed=4))
    run_scenario(s)
    _, recs = _recorded(tmp_path, lambda: run_scenario(s))
    search = _one(recs, "spac.search")
    assert search.attrs["generations"] == 3
    rows = sum(r.attrs["rows"] for r in recs if r.name == "spac.stage2")
    assert search.attrs["evaluated_rows"] == rows > 0


def test_served_requests_are_roots_their_chunks_name(tmp_path):
    base = registry["hft"].override(back_annotation=False, top_k=2,
                                    trace_params={"duration_s": 8e-5})

    def serve():
        eng = DSEServeEngine(slots=2, batch_width=64, verify_width=16)
        reqs = [eng.submit(base, seed=s) for s in (1, 2)]
        eng.run_until_drained()
        return eng, reqs

    serve()
    (eng, reqs), recs = _recorded(tmp_path, serve)
    roots = [r for r in recs if r.name == "spac.serve.request"]
    assert sorted(r.attrs["rid"] for r in roots) == sorted(q.rid for q in reqs)
    assert all(r.parent is None and r.root == r.id and r.attrs["queued_s"] >= 0
               for r in roots)
    chunks = [r for r in recs if r.name == "spac.serve.chunk"]
    assert {rid for c in chunks for rid in c.attrs["ids"]} == {q.rid for q in reqs}
    assert eng.stage2_time_s == pytest.approx(sum(
        c.seconds for c in chunks if c.attrs["kind"] == "surrogate"))
    by_id = {r.id: r for r in recs}
    assert all(by_id[c.parent].name == "spac.serve.tick" for c in chunks)
    assert len([r for r in recs if r.name == "spac.serve.finalize"]) == 2


def test_same_name_joins_and_numbers_add(tmp_path):
    def nested():
        with span("a", rows=1):
            with span("a", rows=2, kind="x"):
                note(rows=4)
            with span("b"):
                note(kind="y")

    _, recs = _recorded(tmp_path, nested)
    a, b = _one(recs, "a"), _one(recs, "b")
    assert a.attrs == {"rows": 7, "kind": "x"}
    assert b.attrs == {"kind": "y"} and b.parent == a.id


def test_device_call_names_its_compile(tmp_path):
    f = jax.jit(lambda x: x * 3)

    def twice():
        for n in (5, 5):
            with span("call", jit=f):
                f(jnp.ones(n)).block_until_ready()

    _, recs = _recorded(tmp_path, twice)
    assert [r.attrs.get("compiled") for r in recs] == [1, None]


def test_ring_overflow_counts_dropped(tmp_path, monkeypatch):
    import collections

    monkeypatch.setattr(spans, "_RING", collections.deque(maxlen=4))

    def many():
        for _ in range(7):
            with span("x"):
                pass

    _, recs = _recorded(tmp_path, many)
    assert len(recs) == 4 and spans.dropped() == 3
    spans.clear()
    assert spans.dropped() == 0
