"""The frozen reference agrees with today's program on the CPU.

At the registry's own trace lengths (the goldens' sizes) the program's
stage-2 rows, stage-4 rows, front and champion equal the serial reference's
on the same trace, and the frozen generators still give the program's
traces.
"""

import json
import os

import numpy as np
import pytest

import check
from conftest import BENCH
from reference import traces as ref_traces


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("generator", ["hft", "datacenter"])
@pytest.mark.parametrize("seed", [0, 3, 2**31 + 5])
def test_generators_match_program(generator, seed):
    from repro.traces.workloads import WORKLOADS

    want = WORKLOADS[generator](seed=seed)
    got = ref_traces.GENERATORS[generator](seed=seed)
    for k in ("time_s", "src", "dst", "payload_bytes"):
        np.testing.assert_array_equal(got[k], getattr(want, k))
    assert got["n_ports"] == want.n_ports
    assert got["link_gbps"] == want.link_gbps


@pytest.mark.parametrize("config,events,search", [
    ("hft8x10g", 2500, False),
    ("dc32x25g", 530, False),
    ("hft8x10g", 600, True),
])
def test_reference_matches_program(tmp_path, config, events, search):
    import dataclasses

    from repro.api import registry, run_scenario
    from repro.api.scenario import SearchSpec, TraceSpec

    import drive

    cfg = _config(config)
    dep = check.deployment(cfg)
    params = dict(cfg["trace"]["params"], duration_s=0.05)
    tr = ref_traces.make_trace(cfg["trace"]["generator"], params, 17, events)
    path = str(tmp_path / "t.npz")
    ref_traces.save_npz(tr, path)
    probe = drive.Probe()
    probe.install()
    s = dataclasses.replace(
        registry[cfg["registry"]].override(**cfg["override"]),
        trace=TraceSpec(path=path))
    if search:
        s = s.override(search=SearchSpec(population=12, generations=3,
                                         seed=5))
    rep = run_scenario(s)
    rows2, rows4 = probe.pop(rep.problem)
    prog = check.program_answers(rows2, rows4, rep.to_dict())
    want = check.reference_answers(dep, tr, prog, search)
    numbers = check.compare(prog, want)
    assert numbers == dict.fromkeys(check.NUMBERS, 0.0), numbers
    assert prog.stage4 and prog.front


def test_program_states_the_configuration(tmp_path):
    import drive

    for name in ("hft8x10g", "dc32x25g"):
        cfg = _config(name)
        params = dict(cfg["trace"]["params"], duration_s=0.05)
        tr = ref_traces.make_trace(cfg["trace"]["generator"], params, 1, 300)
        path = str(tmp_path / f"{name}.npz")
        ref_traces.save_npz(tr, path)
        assert drive.Program(cfg, {}).departures(cfg, path) == []
