"""Tests of the benchmark itself, run on the CPU from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They are not part of the repository's tier-1 suite (``testpaths`` names
``tests`` only).  A tiny copy of the benchmark (``tiny_bench``) keeps every
cell's configuration and limits and cuts only its mix, so a whole run fits
in seconds on the CPU.
"""

import json
import os
import shutil
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
for p in (BENCH, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)

#: per cell: (events, mix changes) of the tiny copy
TINY = {
    "dc32-explore-100k": (600, {"max_rate_per_s": 400, "warm": 1,
                                "check": 1}),
    "dc32-explore-2k": (300, {"max_rate_per_s": 400, "warm": 1, "check": 1}),
    "hft8-nsga2": (256, {"max_rate_per_s": 400, "warm": 1, "check": 1,
                         "search": {"population": 8, "generations": 3},
                         "warm_rows": {"stage2": [], "stage4": []}}),
}


#: cells whose files are kept but which BENCHMARK.json leaves out until the
#: program fault under PERF.md's Open questions is mended: each one's
#: workload entry, as the PR that admits it adds it
UNLISTED = {
    "dc32-explore-100k": {"config": "dc32x25g", "traffic": "explore-100k",
                          "chips": 1, "why": "long scans"},
    "hft8-nsga2": {"config": "hft8x10g", "traffic": "nsga2-4k", "chips": 1,
                   "why": "search layer"},
}


def make_tiny(dest):
    """A copy of the benchmark under ``dest`` whose cells run tiny mixes."""
    bench = os.path.join(dest, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # the unlisted cells still run here, so their files stay tested
    listed = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [dict(w, name=n) for n, w in UNLISTED.items()
                          if n not in listed]
    for w in spec["workloads"]:
        events, changes = TINY[w["name"]]
        with open(os.path.join(bench, "mixes", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        mix.update(changes, events=events)
        w["traffic"] = "tiny-" + w["name"]
        with open(os.path.join(bench, "mixes", w["traffic"] + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dest


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))


def run_tiny(root, argv, capsys, **kw):
    """``harness.main`` on the CPU over the tiny copy; (rc, result, stderr)."""
    import harness

    rc = harness.main(argv, t_start=time.perf_counter(), require_tpu=False,
                      root=root, bench_dir=os.path.join(root, "bench"),
                      cache_dir=os.path.join(root, "jax_cache"), src=SRC,
                      **kw)
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, err
