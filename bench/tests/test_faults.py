"""A run with the timed path broken underneath reports ``correct: false``.

The harness's look for a chip is skipped (``require_tpu=False``); the rest
of a run is driven on the CPU over the tiny copy of each cell, once sound
and once for each fault the cell can have: an answer altered where it is
produced (a stage-4 latency, a stage-4 drop, a stage-2 row's queue
occupancies off by one, one packet's occupancy off by one),
and half of a stage-2 batch left out, its rows answered with the other
half's results.  The explorer keeps no state across steps and runs on one
chip, so the faults of a training step and of the exchange between chips
do not apply.
"""

import numpy as np
import pytest

from conftest import TINY, run_tiny

CELLS = sorted(TINY)


def _alter_stage4(kind):
    import repro.sim.switch_problem as sp

    orig = sp.run_netsim_batched

    def faulty(*a, **kw):
        out = orig(*a, **kw)
        lat = out[0].meta["latency_full_ns"]
        k = int(np.flatnonzero(~np.isnan(lat))[0])
        if kind == "latency":
            lat[k] *= 1.01
        else:
            lat[k] = np.nan
        return out
    return sp, "run_netsim_batched", faulty


def _alter_stage2(kind):
    import repro.sim.switch_problem as sp

    orig = sp.SwitchDSEProblem.surrogate_batch

    def faulty(problem, cands):
        cands = list(cands)
        if kind == "occupancy":
            out = orig(problem, cands)
            out[0].q_occupancy = out[0].q_occupancy + 1   # off by one
            return out
        if kind == "one_packet":
            out = orig(problem, cands)
            occ = np.array(out[-1].q_occupancy)
            occ[occ.size // 2] += 1
            out[-1].q_occupancy = occ
            return out
        half = orig(problem, cands[:max(1, len(cands) // 2)])
        return [half[i % len(half)] for i in range(len(cands))]
    return sp.SwitchDSEProblem, "surrogate_batch", faulty


FAULTS = {
    "stage4_latency": lambda: _alter_stage4("latency"),
    "stage4_drop": lambda: _alter_stage4("drop"),
    "stage2_occupancy": lambda: _alter_stage2("occupancy"),
    "stage2_one_packet": lambda: _alter_stage2("one_packet"),
    "stage2_half_batch": lambda: _alter_stage2("half"),
}


def _argv(cell, seed):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
            "--trace", "0"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_bench, capsys, cell):
    rc, result, err = run_tiny(tiny_bench, _argv(cell, 2**31 + 11), capsys)
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("front_rel: ")


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(tiny_bench, capsys, monkeypatch, cell, fault):
    owner, attr, faulty = FAULTS[fault]()
    monkeypatch.setattr(owner, attr, faulty)
    rc, result, err = run_tiny(tiny_bench, _argv(cell, 23), capsys)
    assert rc == 0, err
    assert result["correct"] is False, result["checks"]


def test_no_tpu_no_result(tiny_bench, capsys):
    import os
    import time

    import harness

    rc = harness.main(_argv(CELLS[0], 1), t_start=time.perf_counter(),
                      root=tiny_bench,
                      bench_dir=os.path.join(tiny_bench, "bench"))
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "needs a TPU" in err and "cpu" in err


def test_benchmark_alone_fails(tiny_bench, capsys):
    """A directory with only BENCHMARK.json and the benchmark's files holds
    no program: the run fails and prints no result."""
    import os
    import time

    import harness

    rc = harness.main(_argv(CELLS[0], 1), t_start=time.perf_counter(),
                      require_tpu=False, root=tiny_bench,
                      bench_dir=os.path.join(tiny_bench, "bench"))
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "program is not" in err


def test_exhausted_pool_is_not_correct(tiny_bench, capsys, monkeypatch):
    """A window that outruns the traces set-up wrote ends as a failure, so
    it never makes traces inside its timing."""
    import traffic

    monkeypatch.setattr(traffic, "pool_size",
                        lambda mix, seconds: int(mix["warm"]))
    rc, result, err = run_tiny(tiny_bench, _argv(CELLS[0], 29), capsys)
    assert rc == 0, err
    assert result["correct"] is False and result["failed"] == 1
    assert "trace pool exhausted" in err
