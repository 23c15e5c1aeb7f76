"""The comparison that decides ``correct``.

What the timed path produced (every stage-2 row, every stage-4 row, the
front and the champion of a sampled exploration) is set against the
serial reference (``reference.switch``) on the same trace.  The numbers
compared, each with its limit in ``limits/<workload>.json``:

  rows_missing         rows one side evaluated and the other did not:
                       stage-2 templates, and stage-3 sized designs (depth
                       included).  Under a search the reference takes the
                       program's stage-2 rows as given and checks the depth
                       each sized design was given.
  occupancy_mismatch   stage-2 packets whose queue occupancy (the count
                       stage 3 sizes each VOQ from) differs; exact
  stage2_latency_rel   largest relative gap of a stage-2 packet latency
  drop_mismatch        stage-4 packets dropped on one side only
  stage4_latency_rel   largest relative gap of a stage-4 packet latency
  front_mismatch       front designs on one side only, plus 1 when the
                       champions differ
  front_rel            largest relative gap of a front design's p99, mean
                       latency, drop rate or throughput

Both sides are put in one plain form first: ``Answers`` holds stage-2 rows
``{Arch: (latency_ns, occupancy)}``, stage-4 rows ``{Arch: latency_ns}``
(NaN for a dropped packet) and the report's front and champion.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from reference import switch as ref

NUMBERS = ("rows_missing", "occupancy_mismatch", "stage2_latency_rel",
           "drop_mismatch", "stage4_latency_rel", "front_mismatch",
           "front_rel")
FRONT_KEYS = ("p99_latency_ns", "mean_latency_ns", "drop_rate",
              "throughput_gbps")


class Answers(NamedTuple):
    stage2: Dict[ref.Arch, Tuple[np.ndarray, np.ndarray]]
    stage4: Dict[ref.Arch, np.ndarray]
    front: Dict[str, Dict[str, float]]       # short name -> FRONT_KEYS
    best: Optional[str]


def deployment(config: dict) -> ref.Deployment:
    sw = config["switch"]
    return ref.Deployment(
        n_ports=int(sw["n_ports"]),
        fields=tuple((f, int(b)) for f, b in sw["header_fields"]),
        routing_bits=int(sw["routing_bits"]),
        flit_bits=int(sw["flit_bits"]),
        p99_latency_ns=float(sw["sla"]["p99_latency_ns"]),
        drop_rate=float(sw["sla"]["drop_rate"]),
        budget={k: float(v) for k, v in sw["budget"].items()},
        delta=float(sw["delta"]),
        top_k=int(sw["top_k"]))


# ------------------------------------------------------------ plain forms

def arch_of(a) -> ref.Arch:
    """The program's ``SwitchArch`` as a reference ``Arch``."""
    return ref.Arch(int(a.n_ports), int(a.bus_bits), a.fwd.value, a.voq.value,
                    a.sched.value, int(a.voq_depth), int(a.hash_banks),
                    int(a.hash_depth), int(a.islip_iters), int(a.addr_bits))


def program_answers(stage2_rows, stage4_rows, report: dict) -> Answers:
    """From the rows the probe kept (pad rows repeat a real row) and the
    golden-format report dict."""
    s2 = {}
    for a, sr in stage2_rows:
        s2.setdefault(arch_of(a), (np.asarray(sr.latency_ns, np.float64),
                                   np.asarray(sr.q_occupancy, np.int64)))
    s4 = {}
    for a, v in stage4_rows:
        s4.setdefault(arch_of(a), np.asarray(v.meta["latency_full_ns"],
                                             np.float64))
    front = {p["candidate"]: {k: float(p[k]) for k in FRONT_KEYS}
             for p in report["pareto"]}
    return Answers(s2, s4, front, report.get("best"))


def _front_dict(front, best) -> Tuple[Dict[str, Dict[str, float]], Optional[str]]:
    out = {ref.short(a): {"p99_latency_ns": v["p99"],
                          "mean_latency_ns": v["mean"],
                          "drop_rate": v["drop_rate"],
                          "throughput_gbps": v["throughput_gbps"]}
           for a, v in front}
    return out, (ref.short(best[0]) if best is not None else None)


def _row(sr):
    return sr["latency_ns"], sr["occupancy"].astype(np.int64)


def reference_answers(dep: ref.Deployment, trace, prog: Answers,
                      search: bool, dtype=np.float64) -> Answers:
    """The reference's own answers.  Exhaustive: Algorithm 1 from scratch.
    Under a search the questions are the program's own stage-2 rows and
    sized designs; each sized design is re-sized from the reference's
    occupancy of its template."""
    if not search:
        r = ref.explore(dep, trace, dtype)
        s2 = {a: _row(sr) for a, sr in r["stage2"].items()}
        s4 = {a: v["latency_full_ns"] for a, v in r["stage4"].items()}
        front, best = _front_dict(r["front"], r["best"])
        return Answers(s2, s4, front, best)
    rows = list(prog.stage2)
    srs = ref.surrogate(rows, dep, trace, dtype)
    s2 = {a: _row(sr) for a, sr in zip(rows, srs)}
    by_template = {a._replace(voq_depth=0): sr for a, sr in zip(rows, srs)}
    sized: List[ref.Arch] = []
    for a in prog.stage4:
        sr = by_template.get(a._replace(voq_depth=0))
        if sr is not None:
            sized.append(ref.size(a, sr["occupancy"], dep.drop_rate))
    verified = list(zip(sized, ref.verify_rows(sized, dep, trace, dtype)))
    front, best = _front_dict(*ref.front(verified, dep))
    return Answers(s2, {a: v["latency_full_ns"] for a, v in verified},
                   front, best)


# ------------------------------------------------------------- comparison

def _rel(got: np.ndarray, want: np.ndarray) -> float:
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-300)))


def compare(prog: Answers, want: Answers) -> Dict[str, float]:
    out = dict.fromkeys(NUMBERS, 0.0)
    out["rows_missing"] = float(len(set(prog.stage2) ^ set(want.stage2))
                                + len(set(prog.stage4) ^ set(want.stage4)))
    for a in set(prog.stage2) & set(want.stage2):
        (lat, occ), (lat_r, occ_r) = prog.stage2[a], want.stage2[a]
        if lat.shape != lat_r.shape:
            out["rows_missing"] += 1
            continue
        out["occupancy_mismatch"] += float(np.count_nonzero(occ != occ_r))
        out["stage2_latency_rel"] = max(out["stage2_latency_rel"],
                                        _rel(lat, lat_r))
    for a in set(prog.stage4) & set(want.stage4):
        lat, lat_r = prog.stage4[a], want.stage4[a]
        if lat.shape != lat_r.shape:
            out["rows_missing"] += 1
            continue
        gone, gone_r = np.isnan(lat), np.isnan(lat_r)
        out["drop_mismatch"] += float(np.count_nonzero(gone != gone_r))
        both = ~gone & ~gone_r
        out["stage4_latency_rel"] = max(out["stage4_latency_rel"],
                                        _rel(lat[both], lat_r[both]))
    out["front_mismatch"] = float(len(set(prog.front) ^ set(want.front))
                                  + (prog.best != want.best))
    for name in set(prog.front) & set(want.front):
        for k in FRONT_KEYS:
            g, w = prog.front[name][k], want.front[name][k]
            if g != w:
                gap = (math.inf if not (math.isfinite(g) and math.isfinite(w))
                       else abs(g - w) / max(abs(w), 1e-300))
                out["front_rel"] = max(out["front_rel"], gap)
    return out


def occupancy_diffs(prog: Answers, want: Answers,
                    limit: int = 5) -> List[Tuple[str, int, int, int]]:
    """The first stage-2 packets whose occupancy differs, as (row, packet,
    program, reference), for the run's standard error."""
    out = []
    for a in sorted(set(prog.stage2) & set(want.stage2)):
        occ, occ_r = prog.stage2[a][1], want.stage2[a][1]
        if occ.shape != occ_r.shape:
            continue
        for k in np.flatnonzero(occ != occ_r)[:limit - len(out)].tolist():
            out.append((ref.short(a), k, int(occ[k]), int(occ_r[k])))
        if len(out) >= limit:
            break
    return out


def worst(readings: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Each number's largest reading over several compared items."""
    out = dict.fromkeys(NUMBERS, 0.0)
    for r in readings:
        for k in NUMBERS:
            out[k] = max(out[k], r[k])
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)
