"""Segmented netsim-kernel speedup: the PR's headline number, gated.

Times the stage-4 finite-buffer verifier over a 256-candidate *sized* hft
sweep (the production shape: stage-2 surrogate prices the enumerated archs,
stage-3 sizes every depth, stage 4 verifies) on the default batched engine
vs the segmented fixed-point kernel path, warm (compile excluded, best of
3).  The bar is >= 5x; a smaller speedup raises, so the harness records the
suite as failed and exits non-zero — the headline number cannot silently
regress.

Parity is asserted bitwise on every candidate (drop rates, delivered sets,
latency arrays — no tolerance): a speedup measured against diverged results
never lands in ``BENCH_dse.json``.  The report also carries the honest
batch composition — how many of the 256 rows are unique dynamics after
dedup (replicated archs collapse; real NSGA-II generations have the same
property, which is exactly why the dedup exists).

    python -m benchmarks.netsim_kernel
"""

import time

import numpy as np

from .common import emit

BATCH = 256
SPEEDUP_BAR = 5.0
REPEATS = 3


def _best_of(fn, repeats=REPEATS):
    fn()                                   # warm: compile + timeline memo
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()           # spaclint: disable=SPAC203
        out = fn()
        ts.append(time.perf_counter() - t0)
    return out, min(ts)


def run():
    from repro.core import (ArchRequest, bind, compressed_protocol,
                            enumerate_candidates)
    from repro.core.dse import depth_for_drop_rate
    from repro.sim import run_netsim_batched, run_surrogate_batched
    from repro.sim.switch_problem import align_depth_to_bram
    from repro.traces import hft

    bound = bind(compressed_protocol(addr_bits=4, length_bits=6),
                 flit_bits=256)
    tr = hft(seed=0)
    base = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))
    cands = (base * (BATCH // len(base) + 1))[:BATCH]

    # stage-3 sizing, exactly as the pipeline produces the verify batch
    ref2 = run_surrogate_batched(cands, bound, tr, back_annotation=False)
    sized = [a.with_depth(align_depth_to_bram(
                 int(depth_for_drop_rate(sr.q_occupancy, 1e-3) * 1.25) + 1,
                 a.bus_bits))
             for a, sr in zip(cands, ref2.results())]
    uniq = len({(a.short(), a.voq_depth) for a in sized})

    ref4, t_def = _best_of(lambda: run_netsim_batched(
        sized, bound, tr, back_annotation=False, use_kernel=False))
    got4, t_ker = _best_of(lambda: run_netsim_batched(
        sized, bound, tr, back_annotation=False, use_kernel=True))

    parity = all(
        vb.drop_rate == vr.drop_rate
        and vb.p99_latency_ns == vr.p99_latency_ns
        and vb.throughput_gbps == vr.throughput_gbps
        and vb.meta["delivered"] == vr.meta["delivered"]
        and np.array_equal(vb.meta["latency_ns"], vr.meta["latency_ns"])
        for vb, vr in zip(ref4, got4))
    speedup = t_def / t_ker

    m = len(tr)
    emit("netsim_kernel/stage4_default", t_def * 1e6,
         f"{BATCH / t_def:.0f} cand/s over B={BATCH} m={m}")
    emit("netsim_kernel/stage4_kernel", t_ker * 1e6,
         f"{BATCH / t_ker:.0f} cand/s; {uniq} unique dynamics after dedup")
    verdict = "PASS" if speedup >= SPEEDUP_BAR else "FAIL"
    emit("netsim_kernel/stage4_speedup", 0.0,
         f"{speedup:.1f}x ({verdict} >={SPEEDUP_BAR:.0f}x bar)")
    emit("netsim_kernel/stage4_parity", 0.0,
         "PASS bitwise" if parity else "FAIL")

    out = {
        "batch": BATCH, "events": m, "unique_rows": uniq,
        "stage4_default_time_s": t_def, "stage4_kernel_time_s": t_ker,
        "stage4_default_cands_per_sec": BATCH / t_def,
        "stage4_kernel_cands_per_sec": BATCH / t_ker,
        "stage4_speedup": speedup, "speedup_bar": SPEEDUP_BAR,
        "stage4_parity_bitwise": parity,
        "pass": parity and speedup >= SPEEDUP_BAR,
    }
    if not parity:
        raise RuntimeError("kernel path diverged from the oracle engine")
    if speedup < SPEEDUP_BAR:
        raise RuntimeError(f"netsim kernel speedup {speedup:.2f}x is below "
                           f"the {SPEEDUP_BAR:.0f}x bar")
    return out


if __name__ == "__main__":
    run()
