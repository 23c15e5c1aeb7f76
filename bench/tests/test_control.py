"""The control fails: the reference computed in float32 (the precision
below the float64 the configuration states) in the program's place is not
``correct`` by any cell's limits.

This is the control at a size a test run holds; its readings at each
cell's own size come from ``run.py --control 1`` on the chip (PERF.md).
"""

import json
import os

import numpy as np
import pytest

import check
from conftest import BENCH, UNLISTED
from reference import traces as ref_traces

#: cell -> events of the test-sized trace
SIZES = {"dc32-explore-100k": 6000, "dc32-explore-2k": 2048,
         "hft8-nsga2": 4096}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
@pytest.mark.parametrize("cell", sorted(SIZES))
def test_float32_control_fails(cell, seed):
    spec = _load("..", "BENCHMARK.json")
    work = {**UNLISTED, **{w["name"]: w for w in spec["workloads"]}}[cell]
    cfg = _load("configs", f"{work['config']}.json")
    limits = _load("limits", f"{cell}.json")
    dep = check.deployment(cfg)
    params = dict(cfg["trace"]["params"], duration_s=0.01)
    tr = ref_traces.make_trace(cfg["trace"]["generator"], params, seed,
                               SIZES[cell])
    want = check.reference_answers(dep, tr, None, False)
    low = check.reference_answers(dep, tr, None, False, np.float32)
    numbers = check.compare(low, want)
    assert not check.judge(numbers, limits), numbers
