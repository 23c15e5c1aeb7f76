"""Golden-report regression harness: seeded ``ScenarioReport.to_dict()``
snapshots under ``tests/golden/`` are re-run and diffed on every suite run.

Any structural drift (Pareto front membership, stage survivor counts, drop
counts, resource totals, search metadata) fails here with a path-by-path
diff.  The scenarios and the comparison policy live in ``repro.api.golden``,
which ``chip_smoke.py`` shares.

Regenerate after an *intentional* behaviour change with:

    PYTHONPATH=src python -m pytest tests/test_golden.py --update-golden
"""

import json
import os
import subprocess
import sys

import pytest

from repro.api import run_scenario
from repro.api.golden import SCENARIOS, diff_reports

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


# --------------------------------------------------------------------------
# the harness
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_golden_report(name, request):
    update = request.config.getoption("--update-golden")
    path = os.path.join(GOLDEN_DIR, f"{name}.json")
    report = run_scenario(SCENARIOS[name]())
    # round-trip through JSON so the diff sees exactly what's on disk
    got = json.loads(json.dumps(report.to_dict()))
    if update:
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(got, f, indent=2, sort_keys=True)
            f.write("\n")
        pytest.skip(f"regenerated {path}")
    if not os.path.exists(path):
        pytest.fail(f"no golden report at {path}; generate with "
                    "`pytest tests/test_golden.py --update-golden`")
    with open(path) as f:
        want = json.load(f)
    errors = diff_reports(got, want)
    assert not errors, (
        f"{name}: report drifted from {path} "
        f"({len(errors)} mismatch(es)):\n" + "\n".join(errors))


# --------------------------------------------------------------------------
# mesh invariance: the same snapshot must hold on a sharded device mesh
# --------------------------------------------------------------------------

def test_golden_hft_nsga2_mesh_invariant():
    """Re-run ``hft_nsga2`` under 2 simulated host devices and diff against
    the *single-device* golden snapshot.  The goldens are mesh-invariant by
    contract — sharding the batched stages may never shift a front, a drop
    count, or a latency quantile — so no regeneration is allowed here: a
    mismatch is a sharding bug, not snapshot drift."""
    path = os.path.join(GOLDEN_DIR, "hft_nsga2.json")
    if not os.path.exists(path):
        pytest.fail(f"no golden report at {path}; generate with "
                    "`pytest tests/test_golden.py --update-golden` "
                    "(on a single device)")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", """
import json
from repro.api import registry, run_scenario
from repro.api.scenario import MeshSpec, SearchSpec
scenario = registry["hft"].override(
    back_annotation=False,
    search=SearchSpec(population=16, generations=4, seed=7))
report = run_scenario(scenario, mesh=MeshSpec(devices=2))
print(json.dumps(report.to_dict()))
"""], env=env, cwd=repo, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    with open(path) as f:
        want = json.load(f)
    errors = diff_reports(got, want)
    assert not errors, (
        "hft_nsga2 under a 2-device mesh drifted from the single-device "
        f"golden ({len(errors)} mismatch(es)):\n" + "\n".join(errors))


@pytest.mark.parametrize("devices", [2, 8])
def test_golden_fattree_mesh_invariant(devices):
    """Hop-composed fabric evaluation must be bit-identical whether the
    batched stages run on 1, 2, or 8 forced host devices: per-hop departures
    feed the next hop, so any sharding drift would compound.  Diff against
    the single-device golden — no regeneration allowed."""
    path = os.path.join(GOLDEN_DIR, "fattree_dc.json")
    if not os.path.exists(path):
        pytest.fail(f"no golden report at {path}; generate with "
                    "`pytest tests/test_golden.py --update-golden` "
                    "(on a single device)")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", f"""
import json
from repro.api import registry, run_scenario
from repro.api.scenario import MeshSpec
scenario = registry["fattree_dc"].override(back_annotation=False)
report = run_scenario(scenario, mesh=MeshSpec(devices={devices}))
print(json.dumps(report.to_dict()))
"""], env=env, cwd=repo, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    with open(path) as f:
        want = json.load(f)
    errors = diff_reports(got, want)
    assert not errors, (
        f"fattree_dc under a {devices}-device mesh drifted from the "
        f"single-device golden ({len(errors)} mismatch(es)):\n"
        + "\n".join(errors))


# --------------------------------------------------------------------------
# the harness's own teeth
# --------------------------------------------------------------------------

def test_diff_catches_exact_drift():
    want = {"best_verify": {"drop_rate": 0.0, "p99_latency_ns": 100.0},
            "resources": {"brams": 16.0}}
    got = json.loads(json.dumps(want))
    assert diff_reports(got, want) == []
    got["best_verify"]["drop_rate"] = 1e-9          # drops compare exactly
    assert any("drop_rate" in e for e in diff_reports(got, want))
    got = json.loads(json.dumps(want))
    got["resources"]["brams"] = 17.0                # resources too
    assert any("brams" in e for e in diff_reports(got, want))


def test_diff_latency_rtol_and_structure():
    want = {"best_verify": {"p99_latency_ns": 100.0}, "pareto": [1, 2]}
    got = {"best_verify": {"p99_latency_ns": 100.0 * (1 + 1e-9)},
           "pareto": [1, 2]}
    assert diff_reports(got, want) == []            # inside rtol
    got["best_verify"]["p99_latency_ns"] = 101.0    # outside rtol
    assert any("p99_latency_ns" in e for e in diff_reports(got, want))
    assert any("length" in e
               for e in diff_reports({"best_verify": {}, "pareto": [1]}, want))
