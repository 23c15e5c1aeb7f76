"""Golden-report scenarios and the comparison every golden check uses.

The pipeline is seeded end to end (trace generators, the NSGA-II engine, the
comm router), so reports are reproducible.  ``SCENARIOS`` names the seeded
scenarios whose ``ScenarioReport.to_dict()`` snapshots live in
``tests/golden/``; ``diff_reports`` is the path-by-path comparison shared by
the golden tests and ``chip_smoke.py``.  Comparison policy:

  * timing fields (``*_time_s``) are volatile and skipped,
  * numbers under latency / throughput / hypervolume keys compare with
    ``rtol=1e-6`` (float-op ordering may differ across BLAS/libm builds),
  * everything else — drops, resources, candidate shorts, stage logs —
    compares exactly.
"""

from __future__ import annotations

import math

from repro.core.dse import ResourceBudget, SLA

from .registry import registry
from .scenario import CommModelSpec, Fidelity, Scenario, SearchSpec

__all__ = ["RTOL", "RTOL_KEYS", "VOLATILE", "SCENARIOS", "diff_reports"]

#: substrings of the *nearest dict key* that switch a float to rtol compare
RTOL_KEYS = ("latency", "throughput")
RTOL = 1e-6
#: report keys that are timing noise, skipped entirely
VOLATILE = ("wall_time_s", "stage2_time_s", "stage4_time_s")


def _comm_small() -> Scenario:
    """A deliberately small MoE dispatch fabric: the comm-domain pipeline
    (router trace -> analytic surrogate -> capacity sizing -> real fabric
    verify) at a size the tier-1 suite can afford."""
    return Scenario(
        name="comm_small",
        domain="comm",
        comm=CommModelSpec(d_model=128, d_ff=256, n_heads=4, n_kv_heads=2,
                           vocab=256, moe_experts=8, moe_topk=2, batch=2,
                           seq=64, model_tp=4),
        sla=SLA(p99_latency_ns=math.inf, drop_rate=2e-2),
        budget=ResourceBudget({"bytes_per_device": 4e9}),
        fidelity=Fidelity(back_annotation=False, top_k=2),
        notes="small MoE dispatch fabric for the golden-report harness")


#: name -> scenario builder; every entry is fully seeded
SCENARIOS = {
    "hft": lambda: registry["hft"].override(back_annotation=False),
    "datacenter": lambda: registry["datacenter"].override(back_annotation=False),
    "comm_small": _comm_small,
    "hft_nsga2": lambda: registry["hft"].override(
        back_annotation=False,
        search=SearchSpec(population=16, generations=4, seed=7)),
    # protocol co-design: the winning layout (name, per-field widths) is part
    # of the snapshot, so protocol genes are locked down bit-for-bit
    "hft_codesign": lambda: registry["hft"].override(
        back_annotation=False, co_design=True,
        search=SearchSpec(population=16, generations=4, seed=7)),
    # multi-hop fabric: the snapshot carries end-to-end p50/p99 and per-tier
    # drop counts the single-switch path cannot express
    "fattree_dc": lambda: registry["fattree_dc"].override(
        back_annotation=False),
}


# --------------------------------------------------------------------------
# structural diff
# --------------------------------------------------------------------------

def _is_rtol_key(key: str) -> bool:
    return any(tag in key for tag in RTOL_KEYS)


def _num_close(a, b) -> bool:
    if math.isinf(a) or math.isinf(b):
        return a == b
    return abs(a - b) <= RTOL * max(abs(a), abs(b), 1e-300)


def diff_reports(got, want, *, path="report", key="", errors=None):
    """Path-by-path diff of two report dicts; returns a list of mismatches."""
    errors = [] if errors is None else errors
    if isinstance(want, dict):
        if not isinstance(got, dict):
            errors.append(f"{path}: expected dict, got {type(got).__name__}")
            return errors
        missing = sorted(set(want) - set(got))
        extra = sorted(set(got) - set(want))
        if missing:
            errors.append(f"{path}: missing keys {missing}")
        if extra:
            errors.append(f"{path}: unexpected keys {extra}")
        for k in sorted(set(want) & set(got)):
            if k in VOLATILE:
                continue
            diff_reports(got[k], want[k], path=f"{path}.{k}", key=k,
                         errors=errors)
    elif isinstance(want, list):
        if not isinstance(got, list) or len(got) != len(want):
            errors.append(f"{path}: length {len(got) if isinstance(got, list) else type(got).__name__} != {len(want)}")
            return errors
        for i, (g, w) in enumerate(zip(got, want)):
            diff_reports(g, w, path=f"{path}[{i}]", key=key, errors=errors)
    elif isinstance(want, str) and want.startswith("hypervolume="):
        # search stage note: embedded float compares with rtol
        if not (isinstance(got, str) and got.startswith("hypervolume=")):
            errors.append(f"{path}: {got!r} != {want!r}")
        elif not _num_close(float(got.split("=", 1)[1]),
                            float(want.split("=", 1)[1])):
            errors.append(f"{path}: {got!r} !~ {want!r}")
    elif isinstance(want, float) and not isinstance(want, bool) and _is_rtol_key(key):
        if not (isinstance(got, (int, float)) and _num_close(float(got), want)):
            errors.append(f"{path}: {got!r} !~ {want!r} (rtol={RTOL})")
    else:
        # drops, resources, counts, candidate shorts: exact
        if got != want:
            errors.append(f"{path}: {got!r} != {want!r}")
    return errors
