"""Mesh-sharded DSE scaling: stage-2/stage-4 cand/s over 1, 2, 4, ... devices.

Runs the batched surrogate (stage 2) and finite-buffer verifier (stage 4)
over a 256-candidate batch at ``MeshSpec(devices=d)`` for every power of two
d up to ``jax.device_count()``, in the calling process — the process that
holds the devices (chips, or simulated host devices forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  With fewer than two
devices there is no curve to measure, and the suite fails.  Simulated
devices share the host's physical cores, so the ideal aggregate throughput
of an N-device mesh is ``serial * min(N, host_cores)`` — per-device
efficiency is measured against that.  The bar is >= 0.7x per-device
efficiency at the widest mesh: sharding dispatch overhead may cost at most
30% of the throughput the hardware can provide.

Correctness is asserted, not sampled: every device count must produce
bitwise-identical stage-2/stage-4 arrays and an identical NSGA-II Pareto
front (the determinism contract from ``tests/test_mesh_dse.py``), so a
scaling number from a silently-diverged shard can never land in
``BENCH_dse.json``.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.mesh_scaling
"""

import os
import time

from .common import emit

BATCH = 256
EFFICIENCY_BAR = 0.7


def device_counts(available: int):
    """1, 2, 4, ... up to ``available``; raises below two devices."""
    if available < 2:
        raise RuntimeError(
            f"mesh_scaling needs at least 2 devices, found {available} (on "
            "the CPU, force host devices with XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")
    counts = [1]
    while counts[-1] * 2 <= available:
        counts.append(counts[-1] * 2)
    return tuple(counts)


def measure() -> dict:
    """Measure every device count in this process; returns the result."""
    import jax
    import numpy as np

    from repro.api import registry, run_scenario
    from repro.api.scenario import MeshSpec, SearchSpec
    from repro.core import (ArchRequest, bind, compressed_protocol,
                            enumerate_candidates)
    from repro.core.dse import depth_for_drop_rate
    from repro.sim import run_surrogate_batched
    from repro.sim.batched_netsim import run_netsim_batched
    from repro.sim.switch_problem import align_depth_to_bram
    from repro.traces import hft

    counts = device_counts(jax.device_count())
    bound = bind(compressed_protocol(addr_bits=4, length_bits=6),
                 flit_bits=256)
    tr = hft(seed=0)
    base = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))
    cands = (base * (BATCH // len(base) + 1))[:BATCH]

    def best_of(fn, reps=3):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            ts.append(time.perf_counter() - t0)
        return out, min(ts)

    # stage-2 reference + stage-3 sizing once (mesh-invariant by contract)
    ref2 = run_surrogate_batched(cands, bound, tr, back_annotation=False)
    sized = [a.with_depth(align_depth_to_bram(
                 int(depth_for_drop_rate(sr.q_occupancy, 1e-3) * 1.25) + 1,
                 a.bus_bits))
             for a, sr in zip(cands, ref2.results())]
    ref4 = run_netsim_batched(sized, bound, tr, back_annotation=False)

    scn = registry["hft"].override(
        back_annotation=False,
        search=SearchSpec(population=16, generations=3, seed=7))
    ref_front = sorted(c["candidate"]
                       for c in run_scenario(scn).to_dict()["pareto"])

    stage2, stage4 = {}, {}
    bitwise = pareto = True
    for d in counts:
        mesh = None if d == 1 else MeshSpec(devices=d)
        f2 = lambda: run_surrogate_batched(cands, bound, tr,
                                           back_annotation=False, mesh=mesh)
        f4 = lambda: run_netsim_batched(sized, bound, tr,
                                        back_annotation=False, mesh=mesh)
        r2, e2 = best_of(f2)
        r4, e4 = best_of(f4)
        stage2[d] = BATCH / e2
        stage4[d] = BATCH / e4
        # bitwise identity at every point — no allclose, no tolerance
        bitwise &= bool(np.array_equal(ref2.latency_ns, r2.latency_ns)
                        and np.array_equal(ref2.q_occupancy, r2.q_occupancy)
                        and np.array_equal(ref2.dep_end_s, r2.dep_end_s))
        bitwise &= all(vb.drop_rate == vr.drop_rate
                       and np.array_equal(vb.meta["latency_ns"],
                                          vr.meta["latency_ns"])
                       for vb, vr in zip(ref4, r4))
        front = sorted(c["candidate"] for c in
                       run_scenario(scn, mesh=mesh).to_dict()["pareto"])
        pareto &= front == ref_front

    cores = os.cpu_count() or 1
    n_max = counts[-1]
    ideal = min(n_max, cores)            # simulated devices share host cores
    eff2 = (stage2[n_max] / stage2[1]) / ideal
    eff4 = (stage4[n_max] / stage4[1]) / ideal
    return {
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": jax.device_count()},
        "device_counts": list(counts), "batch": BATCH,
        "host_cores": cores, "ideal_speedup": ideal,
        "stage2_cands_per_sec": {str(d): stage2[d] for d in counts},
        "stage4_cands_per_sec": {str(d): stage4[d] for d in counts},
        "stage2_efficiency": eff2, "stage4_efficiency": eff4,
        "efficiency_bar": EFFICIENCY_BAR,
        "stage2_pass": eff2 >= EFFICIENCY_BAR,
        "stage4_pass": eff4 >= EFFICIENCY_BAR,
        "bitwise_identical": bitwise, "pareto_identical": pareto,
    }


def run():
    res = measure()
    dev = res["device"]
    for d in res["device_counts"]:
        c2 = res["stage2_cands_per_sec"][str(d)]
        c4 = res["stage4_cands_per_sec"][str(d)]
        emit(f"mesh_scaling/stage2_devices_{d}", 1e6 / c2,
             f"{c2:.0f} cand/s over B={res['batch']}")
        emit(f"mesh_scaling/stage4_devices_{d}", 1e6 / c4,
             f"{c4:.0f} cand/s verify")
    n_max = res["device_counts"][-1]
    note = (f"ideal={res['ideal_speedup']}x on {res['host_cores']} host "
            f"core(s); {dev['count']}x {dev['platform']} {dev['kind']}")
    for stage in ("stage2", "stage4"):
        eff = res[f"{stage}_efficiency"]
        verdict = "PASS" if res[f"{stage}_pass"] else "FAIL"
        emit(f"mesh_scaling/{stage}_efficiency_at_{n_max}", 0.0,
             f"{eff:.2f}x per-device ({verdict} >={EFFICIENCY_BAR}x bar; {note})")
    emit("mesh_scaling/bitwise_identical", 0.0, str(res["bitwise_identical"]))
    emit("mesh_scaling/pareto_identical", 0.0, str(res["pareto_identical"]))
    if not (res["bitwise_identical"] and res["pareto_identical"]):
        raise RuntimeError("sharded results diverged from serial "
                           f"(bitwise={res['bitwise_identical']}, "
                           f"pareto={res['pareto_identical']})")
    return res


if __name__ == "__main__":
    run()
