"""An NSGA-II exploration of the 8-port HFT switch, on the program's normal
path (``run_scenario``), agrees with the serial reference through the
check that decides ``correct``: every stage-2 packet's queue occupancy
exactly, every drop, the front and the champion."""

import dataclasses
import json
import os

import pytest

import check
import drive
from conftest import BENCH
from reference import traces as ref_traces


@pytest.mark.parametrize("seed", [11, 2**31 + 9])
def test_hft_nsga2_matches_reference(tmp_path, seed):
    from repro.api import registry, run_scenario
    from repro.api.scenario import SearchSpec, TraceSpec

    with open(os.path.join(BENCH, "configs", "hft8x10g.json")) as f:
        cfg = json.load(f)
    params = dict(cfg["trace"]["params"], duration_s=0.05)
    tr = ref_traces.make_trace(cfg["trace"]["generator"], params, seed, 512)
    path = str(tmp_path / "t.npz")
    ref_traces.save_npz(tr, path)
    probe = drive.Probe()
    probe.install()
    s = dataclasses.replace(
        registry[cfg["registry"]].override(**cfg["override"]),
        trace=TraceSpec(path=path),
        search=SearchSpec(population=8, generations=4, seed=seed % 1000))
    rep = run_scenario(s)
    rows2, rows4 = probe.pop(rep.problem)
    prog = check.program_answers(rows2, rows4, rep.to_dict())
    want = check.reference_answers(check.deployment(cfg), tr, prog, True)
    assert check.compare(prog, want) == dict.fromkeys(check.NUMBERS, 0.0)
    assert rows2 and prog.stage4 and prog.front
