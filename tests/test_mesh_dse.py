"""Multi-device determinism matrix for the sharded DSE hot path.

The sharding contract (see ``docs/architecture.md`` "Mesh sharding &
elastic resume") is not "close enough" — it is *bit-identical*: the stage-2
and stage-4 scans are rowwise over the candidate axis, so any shard_map
partition of the batch must reproduce the serial recurrence exactly, and
NSGA-II state never touches the mesh, so a checkpoint written on N devices
must resume on M with the same fronts, hv history and RNG stream.  These
tests force 8 simulated host devices in subprocesses (the main session keeps
its single real device) and assert equality with ``assert_array_equal``,
never ``allclose``.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "src"))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def _forced_devices_available() -> bool:
    """Skip-clean guard: some backends ignore the host-device-count flag."""
    try:
        out = _run("import jax; print(jax.device_count())")
    except AssertionError:
        return False
    return out.strip().endswith("8")


_HAVE_8 = None


def _require_forced_devices():
    global _HAVE_8
    if _HAVE_8 is None:
        _HAVE_8 = _forced_devices_available()
    if not _HAVE_8:
        pytest.skip("cannot force 8 simulated host devices on this backend")


# --------------------------------------------------------------------------
# (a) + (d): engine-level bit-identity, incl. non-divisible batch sizes
# --------------------------------------------------------------------------

def test_stage2_stage4_bit_identical_across_device_counts():
    """1-vs-2-vs-8-device (and 2x2-mesh) batch results are bitwise equal:
    latency arrays under the scoped f64 scan, exact drop counts, occupancy,
    departure times — at B=21 (not divisible by 2 or 8, so padding is
    exercised on every mesh)."""
    _require_forced_devices()
    _run("""
import numpy as np
from repro.core import ArchRequest, bind, compressed_protocol, enumerate_candidates
from repro.launch.mesh import MeshSpec
from repro.sim import run_surrogate_batched
from repro.sim.batched_netsim import run_netsim_batched
from repro.traces import hft

BOUND = bind(compressed_protocol(addr_bits=4, length_bits=6), flit_bits=256)
tr = hft(seed=0)
cands = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:21]
meshes = [None, MeshSpec(devices=2), MeshSpec(devices=8),
          MeshSpec(devices=2, scenario_axis=2), MeshSpec(devices=4, scenario_axis=2)]

base2 = run_surrogate_batched(cands, BOUND, tr, back_annotation=False)
base4 = run_netsim_batched(cands, BOUND, tr, back_annotation=False)
for mesh in meshes[1:]:
    r2 = run_surrogate_batched(cands, BOUND, tr, back_annotation=False, mesh=mesh)
    np.testing.assert_array_equal(base2.latency_ns, r2.latency_ns)
    np.testing.assert_array_equal(base2.q_occupancy, r2.q_occupancy)   # drops exact
    np.testing.assert_array_equal(base2.dep_end_s, r2.dep_end_s)
    np.testing.assert_array_equal(base2.throughput_gbps, r2.throughput_gbps)
    np.testing.assert_array_equal(base2.line_rate_feasible, r2.line_rate_feasible)
    r4 = run_netsim_batched(cands, BOUND, tr, back_annotation=False, mesh=mesh)
    for vb, vr in zip(base4, r4):
        assert vb.p99_latency_ns == vr.p99_latency_ns
        assert vb.drop_rate == vr.drop_rate
        assert vb.throughput_gbps == vr.throughput_gbps
        np.testing.assert_array_equal(vb.meta["latency_ns"], vr.meta["latency_ns"])
    print("mesh", mesh, "OK")

# padding edges: B=1 and B=axis-1 on the widest mesh
for B in (1, 7):
    m8 = MeshSpec(devices=8)
    r2 = run_surrogate_batched(cands[:B], BOUND, tr, back_annotation=False, mesh=m8)
    np.testing.assert_array_equal(base2.latency_ns[:B], r2.latency_ns)
    np.testing.assert_array_equal(base2.q_occupancy[:B], r2.q_occupancy)
    r4 = run_netsim_batched(cands[:B], BOUND, tr, back_annotation=False, mesh=m8)
    assert len(r4) == B
    for vb, vr in zip(base4[:B], r4):
        assert vb.drop_rate == vr.drop_rate
        np.testing.assert_array_equal(vb.meta["latency_ns"], vr.meta["latency_ns"])
    print("padding B =", B, "OK")
""")


# --------------------------------------------------------------------------
# (b): NSGA-II same-seed fronts identical across device counts
# --------------------------------------------------------------------------

def test_nsga2_front_identical_across_device_counts():
    """The full scenario report — Pareto front membership, hv history notes,
    stage logs, every latency number — is identical whether the batched
    stages ran serial, on 2 or on 8 devices."""
    _require_forced_devices()
    _run("""
import json
from repro.api import registry, run_scenario
from repro.api.scenario import MeshSpec, SearchSpec
from repro.api.golden import diff_reports

scn = registry["hft"].override(
    back_annotation=False, search=SearchSpec(population=16, generations=3, seed=7))
base = json.loads(json.dumps(run_scenario(scn).to_dict()))
for d in (2, 8):
    got = json.loads(json.dumps(
        run_scenario(scn, mesh=MeshSpec(devices=d)).to_dict()))
    errs = diff_reports(got, base)
    assert not errs, (d, errs[:10])
    print("devices", d, "report identical OK")
""")


# --------------------------------------------------------------------------
# (c): remesh-proof checkpoints — N devices -> M devices, bit-identical
# --------------------------------------------------------------------------

def test_checkpoint_remesh_resume_bit_identical():
    """A search checkpointed mid-run on N devices and resumed on M != N
    matches the uninterrupted serial run bit-for-bit: final front, hv
    history, and the engine's *next* RNG draws."""
    _require_forced_devices()
    _run("""
import shutil
import numpy as np
from repro.api import registry
from repro.api.runner import build_problem
from repro.api.scenario import MeshSpec, SearchSpec
from repro.core.search import load_search_state, run_search

scn = registry["hft"].override(
    back_annotation=False, search=SearchSpec(population=16, generations=4, seed=7))

def search(mesh, ckpt=None, resume=False, cut=None):
    problem, sla, _ = build_problem(scn, mesh=mesh)
    return run_search(problem, scn.search, sla, delta=scn.fidelity.delta,
                      checkpoint_dir=ckpt, resume=resume,
                      max_generations_this_run=cut)

def front(outcome):
    return sorted(c.short() for c, _ in outcome.valid)

ref_ckpt = "/tmp/mesh_dse_ref"
shutil.rmtree(ref_ckpt, ignore_errors=True)
ref = search(None, ckpt=ref_ckpt)           # uninterrupted serial, checkpointed

for n, m in ((8, 2), (2, 8)):
    ckpt = f"/tmp/mesh_dse_{n}to{m}"
    shutil.rmtree(ckpt, ignore_errors=True)
    search(MeshSpec(devices=n), ckpt=ckpt, cut=2)          # killed mid-run on N
    out = search(MeshSpec(devices=m), ckpt=ckpt, resume=True)  # resumed on M
    assert front(out) == front(ref), (n, m)
    # hv history and next RNG draws from the final checkpointed state
    prob, _, _ = build_problem(scn)
    eng_a = load_search_state(ckpt, prob.space(), scn.search)
    eng_b = load_search_state(ref_ckpt, prob.space(), scn.search)
    assert eng_a.hv_history == eng_b.hv_history, (n, m)
    np.testing.assert_array_equal(eng_a.rng.random(16), eng_b.rng.random(16))
    print(f"{n}->{m} resume bit-identical OK")
""")


# --------------------------------------------------------------------------
# satellite fix: loud failures instead of silently-wrong shardings
# --------------------------------------------------------------------------

def test_mesh_validation_names_both_numbers():
    import jax

    from repro.launch.mesh import MeshSpec, make_mesh

    with pytest.raises(ValueError, match=r"extent 0"):
        make_mesh((0, 1), ("scenario", "cand"))
    avail = jax.device_count()
    with pytest.raises(ValueError) as ei:
        make_mesh((avail + 1, 1), ("scenario", "cand"))
    assert str(avail + 1) in str(ei.value) and str(avail) in str(ei.value)
    with pytest.raises(ValueError, match=r"size 0"):
        MeshSpec(devices=0)
    with pytest.raises(ValueError, match=r"size 0"):
        MeshSpec(scenario_axis=0)
    with pytest.raises(ValueError) as ei:
        MeshSpec(devices=avail + 3).build()
    assert str(avail + 3) in str(ei.value) and str(avail) in str(ei.value)


def test_remesh_rejects_oversized_target(monkeypatch, mesh11):
    import jax

    from repro.runtime.elastic import remesh

    monkeypatch.setattr(jax, "device_count", lambda: 0)
    with pytest.raises(ValueError) as ei:
        remesh({"x": 1.0}, {"x": jax.sharding.PartitionSpec()}, mesh11)
    msg = str(ei.value)
    assert "needs 1" in msg and "only 0" in msg
