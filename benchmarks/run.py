"""Benchmark harness — one entry per paper table/figure + the TPU-side fabric
microbench and the dry-run roofline table.

Emits ``name,us_per_call,derived`` CSV rows (derived strings use ';'
separators so the CSV stays 3 columns).  With ``--json [PATH]`` the suites'
structured returns are also written as one machine-readable file (default
``BENCH_dse.json``) — stage-2/stage-4 candidates/sec, end-to-end scenario
wall-clock and Pareto sizes from ``dse_throughput`` — so the performance
trajectory is diffable across commits (CI uploads it as an artifact).

    python -m benchmarks.run                      # everything (pip install -e . once)
    python -m benchmarks.run fig7 table2
    python -m benchmarks.run --json dse_throughput
    python -m benchmarks.run --json bench.json dse_throughput
"""

import json
import sys
import time
import traceback

from . import (dse_throughput, fabric_scaling, fig1_sensitivity, fig6_fidelity,
               fig7_dse_pareto, fig8_scaling, mesh_scaling, moe_fabric,
               netsim_kernel, roofline_table, search_quality, serve_throughput,
               table1_resources, table2_adaptation)

SUITES = {
    "table1": table1_resources.run,
    "fig1": fig1_sensitivity.run,
    "fig6": fig6_fidelity.run,
    "fig7": fig7_dse_pareto.run,
    "fig8": fig8_scaling.run,
    "table2": table2_adaptation.run,
    # the header-adaptation row alone (42B Ethernet vs co-designed layout,
    # domination + stage-2 throughput bars) — cheap enough for CI smoke
    "table2_header": table2_adaptation.header_adaptation,
    "roofline": roofline_table.run,
    "moe_fabric": moe_fabric.run,
    "dse_throughput": dse_throughput.run,
    "search": search_quality.run,
    # device-mesh sharding: stage-2/stage-4 cand/s over 1, 2, 4, ... of this
    # process's devices + bitwise/Pareto identity asserts (fails below 2)
    "mesh_scaling": mesh_scaling.run,
    # segmented netsim kernels vs the oracle engines on a 256-candidate
    # sized hft sweep — >=5x stage-4 bar + bitwise parity, both hard-fail
    "netsim_kernel": netsim_kernel.run,
    # 64 interleaved requests through the continuously-batched DSE service:
    # aggregate stage-2 cand/s >= the batched campaign path, mean request
    # latency far below 64 serial runs, cache hit counters asserted
    "serve": serve_throughput.run,
    # multi-hop fabric verify over ring/leaf-spine/fat-tree topologies:
    # cand/s + hop-normalised cand*hops/s, 1-hop bitwise identity asserted
    "fabric_scaling": fabric_scaling.run,
}

DEFAULT_JSON = "BENCH_dse.json"


def _jsonable(obj):
    """Best-effort scalarisation so numpy types survive json.dump."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if hasattr(obj, "item"):          # numpy scalar
        return obj.item()
    return str(obj)


def main() -> None:
    argv = list(sys.argv[1:])
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        argv.pop(i)
        json_path = DEFAULT_JSON
        if i < len(argv) and argv[i] not in SUITES and not argv[i].startswith("-"):
            json_path = argv.pop(i)
    wanted = [a for a in argv if a in SUITES] or list(SUITES)
    print("name,us_per_call,derived")
    failures = []
    results = {}
    wall = {}
    for name in wanted:
        t0 = time.time()
        try:
            out = SUITES[name]()
            if isinstance(out, dict):
                results[name] = _jsonable(out)
            wall[name] = time.time() - t0
            print(f"{name}/TOTAL,{wall[name] * 1e6:.0f},ok")
        except Exception:  # noqa: BLE001 - keep the harness running
            failures.append(name)
            wall[name] = time.time() - t0
            traceback.print_exc()
            print(f"{name}/TOTAL,{wall[name] * 1e6:.0f},FAILED")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"suites": results, "suite_wall_s": wall,
                       "failures": failures}, f, indent=2, sort_keys=True)
        print(f"wrote {json_path}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
