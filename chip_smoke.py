"""On-chip smoke test of the SPAC explorer's main path.

Drives protocol DSL -> ``Scenario`` -> Algorithm 1 -> verified Pareto front
through the public entry points (``repro.api.run_scenario`` and the
``DSEServeEngine`` behind ``spac serve``) on a TPU, in this one process, and
checks what comes out with the repository's own references:

  (a) device      fail unless JAX's first device is a TPU;
  (b) goldens     the six seeded golden scenarios, diffed against
                  ``tests/golden/*.json`` with ``repro.api.golden.diff_reports``
                  (exact drops, resources and fronts; rtol 1e-6 latencies);
  (c) datacenter  the registry's 32-port x 25 Gb/s switch on a trace of
                  >= 1e5 events; every front candidate is re-verified by the
                  serial reference ``sim.netsim.run_netsim`` on the same
                  trace: drop counts exact, per-packet latency within rtol
                  1e-6;
      escalate    the same switch on the registry's own trace with
                  ``verify_engine="auto"``: the front equals the golden one,
                  and the champion's climb to the cycle-accurate datapath
                  (one scan step per clock cycle) returns finite metrics;
  (d) serve       hft, datacenter, fattree_dc and one repeat through
                  ``DSEServeEngine``: no request errors, and every served
                  report equal to ``run_scenario``'s;
  (e) timing      wall-clock and XLA compile seconds per phase.

Timings are chip wall-clock for this smoke run, not benchmark results.  The
last line of standard output is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed; any failure exits non-zero.

    python chip_smoke.py                # one chip: every phase above
    python chip_smoke.py --four-chips   # hft_nsga2 and the 1e5-event
                                        # datacenter at MeshSpec(devices=4)
                                        # and devices=1, reports compared
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
GOLDEN_DIR = os.path.join(REPO, "tests", "golden")
#: trace length of phase (c): the registry's 800 us holds 530 events, 170 ms
#: about 1.05e5 (seed 0)
DATACENTER_DURATION_S = 0.17
MIN_EVENTS = 100_000
LATENCY_RTOL = 1e-6
FOUR_CHIPS = 4


class CompileClock:
    """Sums XLA backend-compile seconds (cache retrievals included) from
    JAX's monitoring events, so each phase can report its compile share."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def _say(msg: str) -> None:
    print(msg, flush=True)


def _report_dict(report) -> dict:
    """What the golden files hold: the report after a JSON round trip."""
    return json.loads(json.dumps(report.to_dict()))


def _golden(name: str) -> dict:
    with open(os.path.join(GOLDEN_DIR, f"{name}.json")) as f:
        return json.load(f)


def _datacenter_1e5():
    from repro.api import registry

    # back_annotation=False as in the golden variant.  A trace this long
    # holds 4-byte packets, whose line-rate bound at 25 Gb/s makes stage 1
    # prune every design at the registry's delta; 2.5 is the relaxation the
    # registry's fattree_dc gives the same trace generator, for that reason
    return registry["datacenter"].override(
        back_annotation=False, delta=2.5,
        trace_params={"duration_s": DATACENTER_DURATION_S})


# --------------------------------------------------------------------------
# phases: each returns (ok, facts) and prints its own lines
# --------------------------------------------------------------------------

def phase_goldens(state: dict) -> bool:
    from repro.api import run_scenario
    from repro.api.golden import SCENARIOS, diff_reports

    matched = 0
    for name in sorted(SCENARIOS):
        report = run_scenario(SCENARIOS[name]())
        got = _report_dict(report)
        state.setdefault("reports", {})[name] = got
        errors = diff_reports(got, _golden(name))
        if errors:
            _say(f"  golden {name}: {len(errors)} mismatch(es); first: "
                 f"{errors[0]}")
            for e in errors[1:5]:
                _say(f"    {e}")
        else:
            matched += 1
            _say(f"  golden {name}: match")
    _say(f"goldens matched: {matched}/{len(SCENARIOS)}")
    return matched == len(SCENARIOS)


def _verify_front_against_serial(report) -> dict:
    """Re-verify every front candidate with the serial heapq engine on the
    report's own trace; returns the comparison facts."""
    import numpy as np

    from repro.sim.netsim import run_netsim

    problem = report.problem
    trace = problem.trace
    t0 = np.asarray(trace.time_s, np.float64)
    out = {"front": len(report.pareto), "drops_exact": True,
           "max_rel_latency": 0.0, "max_rel_departure": 0.0,
           "first_diff": None}
    for arch, v in report.pareto:
        ref = run_netsim(arch, problem.bound, trace, hw=v.meta["hw"])
        got_l = np.asarray(v.meta["latency_full_ns"], np.float64)
        ref_l = np.asarray(ref.meta["latency_full_ns"], np.float64)
        same_set = bool(np.array_equal(np.isnan(got_l), np.isnan(ref_l)))
        if not same_set or v.drop_rate != ref.drop_rate:
            out["drops_exact"] = False
            out["first_diff"] = out["first_diff"] or (
                f"{arch.short()}: drop_rate {v.drop_rate!r} vs serial "
                f"{ref.drop_rate!r}")
            continue
        ok = ~np.isnan(ref_l)
        if not ok.any():
            continue
        rel = np.abs(got_l[ok] - ref_l[ok]) / np.maximum(np.abs(ref_l[ok]),
                                                         1e-300)
        # departure instants (s) = latency - propagation + generation time
        dep_g = got_l[ok] * 1e-9 + t0[ok]
        dep_r = ref_l[ok] * 1e-9 + t0[ok]
        rel_d = np.abs(dep_g - dep_r) / np.maximum(np.abs(dep_r), 1e-300)
        out["max_rel_latency"] = max(out["max_rel_latency"], float(rel.max()))
        out["max_rel_departure"] = max(out["max_rel_departure"],
                                       float(rel_d.max()))
        if rel.max() > LATENCY_RTOL and out["first_diff"] is None:
            k = int(np.flatnonzero(ok)[int(rel.argmax())])
            out["first_diff"] = (f"{arch.short()}: packet {k} latency "
                                 f"{got_l[k]!r} ns vs serial {ref_l[k]!r} ns")
    return out


def phase_datacenter(state: dict) -> bool:
    from repro.api import run_scenario

    report = run_scenario(_datacenter_1e5())
    state["datacenter_1e5"] = _report_dict(report)
    events = int(report.problem.trace.time_s.size)
    _say(f"  datacenter trace: {events} events "
         f"({DATACENTER_DURATION_S * 1e3:g} ms at "
         f"{report.problem.trace.link_gbps:g} Gb/s)")
    for lg in report.result.logs:
        _say(f"  stage {lg.stage}: {lg.considered} -> {lg.survived}")
    rows = report.result.evaluated
    fallback = [v.meta.get("fallback") for _, v, *_ in rows
                if v.meta.get("fallback")]
    _say(f"  stage-4 rows: {len(rows)}, serial-fallback rows: "
         f"{len(fallback)} {sorted(set(fallback)) if fallback else ''}")
    cmp = _verify_front_against_serial(report)
    _say(f"  front vs serial run_netsim: {cmp['front']} candidate(s), drops "
         f"exact={cmp['drops_exact']}")
    _say(f"  stage-4 max relative difference vs run_netsim: "
         f"latency={cmp['max_rel_latency']!r} "
         f"departure={cmp['max_rel_departure']!r}")
    if cmp["first_diff"]:
        _say(f"  first differing row: {cmp['first_diff']}")
    return (events >= MIN_EVENTS and cmp["front"] > 0 and cmp["drops_exact"]
            and cmp["max_rel_latency"] <= LATENCY_RTOL)


def phase_escalate(state: dict) -> bool:
    """``verify_engine="auto"`` on the registry-length datacenter trace: the
    cycle-accurate rung steps once per clock cycle, so it is run here and not
    on the 1e5-event trace, whose ~1e7 cycles no smoke run can afford."""
    import math

    from repro.api import run_scenario
    from repro.api.golden import SCENARIOS, diff_reports

    report = run_scenario(SCENARIOS["datacenter"]().override(
        verify_engine="auto"))
    got = {k: v for k, v in _report_dict(report).items() if k != "scenario"}
    want = {k: v for k, v in _golden("datacenter").items() if k != "scenario"}
    errors = diff_reports(got, want)
    _say(f"  datacenter verify_engine=auto: front vs golden "
         f"{'match' if not errors else f'{len(errors)} mismatch(es)'}")
    for e in errors[:5]:
        _say(f"    {e}")
    esc = report.best_verify.meta.get("escalated") if report.best_verify else None
    if esc is None:
        _say("  champion was not escalated to the cycle-accurate datapath")
        return False
    cyc = esc.meta["cycle"]
    _say(f"  champion on the cycle-accurate datapath: {cyc.n_cycles} cycles, "
         f"p99={esc.p99_latency_ns!r} ns mean={esc.mean_latency_ns!r} ns "
         f"drop_rate={esc.drop_rate!r}")
    finite = (math.isfinite(esc.p99_latency_ns)
              and math.isfinite(esc.mean_latency_ns)
              and 0.0 <= esc.drop_rate <= 1.0)
    return not errors and finite


def phase_serve(state: dict) -> bool:
    from repro.api import DSEServeEngine, run_scenario, strip_times
    from repro.api.golden import SCENARIOS

    names = ["hft", "datacenter", "fattree_dc", "hft"]
    engine = DSEServeEngine(slots=4)
    reqs = [engine.submit(SCENARIOS[n]()) for n in names]
    engine.run_until_drained()
    errors = [r for r in reqs if r.error is not None]
    for r in errors:
        _say(f"  request {r.rid} ({r.scenario.name}) error: {r.error}")
    same = 0
    for name, r in zip(names, reqs):
        if r.report is None:
            continue
        want = state.get("reports", {}).get(name)
        if want is None:
            want = _report_dict(run_scenario(SCENARIOS[name]()))
        if strip_times(json.loads(json.dumps(r.report))) == strip_times(want):
            same += 1
        else:
            _say(f"  request {r.rid} ({name}): served report differs from "
                 "run_scenario's")
    st = engine.stats()
    _say(f"  served {len(reqs)} requests: errors={len(errors)}, equal to "
         f"run_scenario={same}/{len(reqs)}, report cache hits="
         f"{st['report_hits']}")
    return not errors and same == len(reqs)


def phase_four_chips(state: dict) -> bool:
    from repro.api import run_scenario, strip_times
    from repro.api.golden import SCENARIOS, diff_reports
    from repro.api.scenario import MeshSpec

    ok = True
    cases = [("hft_nsga2", SCENARIOS["hft_nsga2"]),
             ("datacenter_1e5", _datacenter_1e5)]
    for name, build in cases:
        one_report = run_scenario(build(), mesh=MeshSpec(devices=1))
        one = _report_dict(one_report)
        four = _report_dict(run_scenario(build(),
                                         mesh=MeshSpec(devices=FOUR_CHIPS)))
        # the mesh contract is bit-identity, stricter than the golden rtol
        same = strip_times(four) == strip_times(one)
        _say(f"  {name}: {one_report.problem.trace.time_s.size} events, "
             f"devices={FOUR_CHIPS} vs devices=1: "
             f"{'identical' if same else 'DIFFERENT'}")
        for e in ([] if same else diff_reports(four, one))[:5]:
            _say(f"    {e}")
        ok &= same
        if name in SCENARIOS:
            g_errors = diff_reports(four, _golden(name))
            _say(f"  {name}: golden {'match' if not g_errors else 'MISMATCH'}")
            for e in g_errors[:5]:
                _say(f"    {e}")
            ok &= not g_errors
    return ok


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    _say(f"device: platform={device['platform']} kind={device['kind']} "
         f"count={device['count']}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device['platform']!r}",
              file=sys.stderr)
        return 1
    want = FOUR_CHIPS if args.four_chips else 1
    if device["count"] < want:
        print(f"chip_smoke: needs {want} chip(s), found {device['count']}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package next to this "
              f"script ({e})", file=sys.stderr)
        return 1
    _say(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()

    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("goldens", phase_goldens), ("datacenter", phase_datacenter),
               ("escalate", phase_escalate), ("serve", phase_serve)])
    state: dict = {}
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        _say(f"phase {name}:")
        c0, n0 = clock.seconds, clock.count
        t0 = time.perf_counter()
        try:
            ok = fn(state)
        except Exception:           # a phase that raises has failed
            traceback.print_exc(file=sys.stdout)
            ok = False
        wall = time.perf_counter() - t0
        _say(f"  timing {name} (chip wall-clock, not a benchmark): "
             f"wall={wall:.3f}s compile={clock.seconds - c0:.3f}s "
             f"({clock.count - n0} compiles)")
        _say(f"phase {name}: {'PASS' if ok else 'FAIL'}")
        if not ok:
            failed.append(name)
    _say(f"timing total (chip wall-clock, not a benchmark): "
         f"wall={time.perf_counter() - t_all:.3f}s "
         f"compile={clock.seconds:.3f}s ({clock.count} compiles)")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
