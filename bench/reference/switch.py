"""Plain serial reference of the explorer: Algorithm 1 on one switch.

A frozen, self-contained restatement of what the program computes, written
from ``repro.sim.resources`` (calibrated resource model), ``backannotate``
(analytic hardware parameters), ``core.archspec`` (template enumeration),
``core.dse`` (stages 1-4 and the Pareto front), ``sim.surrogate`` (stage 2)
and ``sim.netsim`` (stage 4) as they stood when the benchmark was defined.
It imports nothing of the program: every design point is a plain ``Arch``
tuple and every trace a dict of arrays (``reference.traces``).

Times are computed in ``dtype``: float64, as the configuration states, or
float32 for the control that a lower precision has to fail.  In float32 every
time is rounded to float32 after each operation, the way a float32 scan
would hold it.
"""

from __future__ import annotations

import heapq
import itertools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Arch", "Deployment", "short", "header_bits", "synthesize",
           "hardware", "idc", "enumerate_templates", "stage1",
           "surrogate", "netsim", "size", "explore", "verify_rows"]

BUS_WIDTHS = (128, 256, 512, 1024)
FWD = ("full_lookup", "multibank_hash")
VOQ = ("nxn", "shared")
SCHED = ("rr", "islip", "edrrm")
BRAM_BITS = 36 * 1024
PROP_DELAY_S = 50e-9
HEADROOM = 1.25


class Arch(NamedTuple):
    """One concrete switch template (the fields ``SwitchArch`` carries)."""

    n_ports: int
    bus_bits: int
    fwd: str
    voq: str
    sched: str
    voq_depth: int = 64
    hash_banks: int = 4
    hash_depth: int = 256
    islip_iters: int = 2
    addr_bits: int = 8


class Deployment(NamedTuple):
    """What a configuration file states about the switch under design."""

    n_ports: int
    fields: Tuple[Tuple[str, int], ...]     # wire header, in order
    routing_bits: int
    flit_bits: int
    p99_latency_ns: float
    drop_rate: float
    budget: Dict[str, float]
    delta: float
    top_k: int


def short(a: Arch) -> str:
    k = {"full_lookup": "Full", "multibank_hash": "MBH"}[a.fwd]
    v = {"nxn": "NxN", "shared": "Shared"}[a.voq]
    return f"{k}/{v}/{a.sched.upper()}@{a.bus_bits}b d{a.voq_depth}"


# ---------------------------------------------------------------- protocol

def header_bits(dep: Deployment) -> int:
    return sum(b for _, b in dep.fields)


def header_bytes(dep: Deployment) -> int:
    return -(-header_bits(dep) // 8)


def straddlers(dep: Deployment) -> int:
    """Fields that cross a flit boundary."""
    n, off = 0, 0
    for _, bits in dep.fields:
        if bits and off // dep.flit_bits != (off + bits - 1) // dep.flit_bits:
            n += 1
        off += bits
    return n


# ----------------------------------------------------------- resource model

def _luts(a: Arch, hb: int, strad: int, key_bits: int) -> float:
    n, w = a.n_ports, a.bus_bits
    parser = n * (80 + 0.2 * hb + 0.002 * hb * w + 60 * strad)
    if a.fwd == "full_lookup":
        fwd = 1.2 * (1 << a.addr_bits) * n
    else:
        fwd = n * (150 + 60 * a.hash_banks + 8 * key_bits)
    crossbar = 0.05 * w * n * n
    voq = 30 * n * n if a.voq == "nxn" else 45 * n * n + 25 * n * n
    sched = {"rr": 3.0 * n * n, "islip": 10.0 * n * n * a.islip_iters,
             "edrrm": 5.0 * n * n}[a.sched]
    meta = 0.5 * n * n * hb
    return parser + fwd + crossbar + voq + sched + meta


def _ffs(a: Arch, hb: int, strad: int) -> float:
    n, w = a.n_ports, a.bus_bits
    return 2.0 * n * w + 1.2 * n * hb + 32.0 * n * strad + 18.0 * n * n


def _brams(a: Arch, key_bits: int) -> float:
    n, w, d = a.n_ports, a.bus_bits, a.voq_depth
    if a.voq == "nxn":
        data_bits, ptr_bits = n * n * d * w, 0
    else:
        data_bits = n * d * w
        ptr_bits = n * n * d * (math.ceil(math.log2(max(n * d, 2))) + n)
    fwd_bits = 0.0
    if a.fwd == "multibank_hash":
        fwd_bits = a.hash_banks * a.hash_depth * (key_bits + 8)
    io_fifos = 2 * n * w * 32
    return (data_bits + ptr_bits + fwd_bits + io_fifos) / BRAM_BITS


def _path_ns(a: Arch) -> float:
    n, w = a.n_ports, a.bus_bits
    log_n = math.log2(max(n, 2))
    paths = [
        2.2 + 0.0006 * w,
        2.0 if a.fwd == "full_lookup"
        else 3.3 + 0.05 * math.log2(max(a.hash_depth, 2)),
        (2.4 + 0.30 * log_n) if a.voq == "nxn" else (3.0 + 0.40 * log_n),
        {"rr": 2.6 + 0.55 * log_n, "islip": 4.0 + 0.75 * log_n,
         "edrrm": 3.1 + 0.65 * log_n}[a.sched],
    ]
    return max(paths) + 0.0017 * w


def _pipeline_cycles(a: Arch, fmax_mhz: float) -> int:
    base = (2 + (1 if a.fwd == "full_lookup" else 2)
            + (1 if a.voq == "nxn" else 2)
            + {"rr": 1, "edrrm": 2, "islip": 1 + a.islip_iters}[a.sched] + 2)
    base += max(0, round(0.4 * (a.n_ports - 8)))
    if fmax_mhz > 250:
        base = int(base * 2)
    return base


#: Table I SPAC rows: (arch, header bits, LUT K, FF K, BRAM, fmax MHz)
_TABLE1 = [
    (Arch(8, 512, "multibank_hash", "nxn", "islip", 320, addr_bits=12), 336,
     80.1, 45.8, 304, 146),
    (Arch(16, 512, "multibank_hash", "nxn", "islip", 160, addr_bits=12), 336,
     315.6, 135.1, 608, 137),
    (Arch(8, 256, "multibank_hash", "nxn", "islip", 640, addr_bits=12), 16,
     38.9, 30.5, 260, 165),
    (Arch(16, 256, "multibank_hash", "nxn", "islip", 320, addr_bits=12), 16,
     96.1, 83.2, 498, 142),
]


def _calibrate() -> Dict[str, float]:
    r = {"luts": [], "ffs": [], "brams": [], "path": []}
    for a, hb, lut_k, ff_k, bram, fmax in _TABLE1:
        r["luts"].append(lut_k * 1e3 / _luts(a, hb, 2, a.addr_bits))
        r["ffs"].append(ff_k * 1e3 / _ffs(a, hb, 2))
        r["brams"].append(bram / max(_brams(a, a.addr_bits), 1e-9))
        r["path"].append((1e3 / fmax) / _path_ns(a))
    return {k: float(np.exp(np.mean(np.log(v)))) for k, v in r.items()}


_CALIB = _calibrate()


def synthesize(a: Arch, dep: Deployment) -> Dict[str, float]:
    hb, strad, kb = header_bits(dep), straddlers(dep), dep.routing_bits
    fmax = min(1e3 / (_CALIB["path"] * _path_ns(a)), 350.0)
    return {"luts": _CALIB["luts"] * _luts(a, hb, strad, kb),
            "ffs": _CALIB["ffs"] * _ffs(a, hb, strad),
            "brams": _CALIB["brams"] * _brams(a, kb),
            "fmax_mhz": fmax,
            "pipeline_cycles": _pipeline_cycles(a, fmax)}


def _eta(a: Arch, i_burst: float) -> float:
    n = a.n_ports
    if a.sched == "islip":
        eta = min(0.96 + 0.02 * (a.islip_iters - 2), 0.99)
        eta -= 0.05 * min(i_burst / 20.0, 1.0)
    elif a.sched == "rr":
        eta = 0.80 + 0.6 / n
    else:
        eta = 0.86 + min(0.012 * i_burst, 0.12)
    return float(min(max(eta, 0.5), 0.995))


def _arb_cycles(a: Arch, i_burst: float) -> float:
    if a.sched == "rr":
        return 0.35 * a.n_ports
    if a.sched == "islip":
        return a.islip_iters + 1.0
    return 2.0 + 0.25 * a.n_ports / max(i_burst, 1.0)


def hardware(a: Arch, dep: Deployment, i_burst: float) -> Dict[str, float]:
    """Analytic hardware parameters (no cycle-level back-annotation)."""
    rep = synthesize(a, dep)
    return {"fclk": rep["fmax_mhz"] * 1e6, "pipeline": rep["pipeline_cycles"],
            "eta": _eta(a, i_burst), "arb": _arb_cycles(a, i_burst),
            "stall": 0.3 if a.fwd == "multibank_hash" else 0.0}


def idc(t: np.ndarray) -> float:
    """Index of dispersion for counts over ~200 windows."""
    t = np.asarray(t, np.float64)
    if t.size < 2:
        return 1.0
    span = float(t.max() - t.min())
    if span <= 0:
        return 1.0
    w = max(span / 200.0, span / max(t.size, 1) * 4)
    counts, _ = np.histogram(t, bins=np.arange(t.min(), t.max() + w, w))
    m = counts.mean()
    return 1.0 if m <= 0 else float(counts.var() / m)


# -------------------------------------------------------------- stages 1-3

def enumerate_templates(dep: Deployment) -> List[Arch]:
    addr = dep.routing_bits
    fwd = [f for f in FWD if not (f == "full_lookup" and addr > 16)] \
        or ["multibank_hash"]
    return [Arch(dep.n_ports, bus, f, v, s, 64, addr_bits=addr)
            for bus, f, v, s in itertools.product(BUS_WIDTHS, fwd, VOQ, SCHED)]


def stage1(archs: Sequence[Arch], dep: Deployment, trace) -> List[Arch]:
    """Static timing prune: one flit of the smallest packet per II."""
    s_min_wire = int(trace["payload_bytes"].min()) + header_bytes(dep)
    t_arrival = s_min_wire * 8 / (trace["link_gbps"] * 1e9)
    out = []
    for a in archs:
        flits = max(1, math.ceil(s_min_wire / (a.bus_bits / 8)))
        t_proc = flits / (synthesize(a, dep)["fmax_mhz"] * 1e6)
        if t_proc <= (1.0 + dep.delta) * t_arrival:
            out.append(a)
    return out


def depth_for_drop_rate(occ: np.ndarray, eps: float) -> int:
    q = np.asarray(occ, np.float64)
    if q.size == 0:
        return 1
    return max(1, int(math.ceil(float(np.quantile(q, 1.0 - eps,
                                                  method="higher")))))


def size(a: Arch, occ: np.ndarray, eps: float) -> Arch:
    """Stage-3 depth: the occupancy quantile at 1 - eps, with headroom,
    rounded up to whole RAMB36 rows."""
    d_opt = depth_for_drop_rate(occ, eps)
    per_bram = max(1, BRAM_BITS // a.bus_bits)
    d = int(math.ceil(max(int(d_opt * HEADROOM) + 1, 1) / per_bram) * per_bram)
    return a._replace(voq_depth=d)


# ------------------------------------------------------------------ stage 2

def _rounder(dtype):
    if np.dtype(dtype) == np.float64:
        return float
    return lambda x: float(np.float32(x))


def surrogate(archs: Sequence[Arch], dep: Deployment, trace,
              dtype=np.float64) -> List[dict]:
    """Infinite-buffer greedy crossbar in arrival order, all candidates at
    once (one loop over events, candidates as a vector)."""
    dt = np.dtype(dtype)
    n = dep.n_ports
    i_burst = idc(trace["time_s"])
    t64 = np.asarray(trace["time_s"], np.float64)
    t = (t64 - t64.min()).astype(dt)
    src = np.asarray(trace["src"], np.int64) % n
    dst = np.asarray(trace["dst"], np.int64) % n
    payload = np.asarray(trace["payload_bytes"], np.int64)
    hbytes = header_bytes(dep)
    m, b_n = t.size, len(archs)
    hws = [hardware(a, dep, i_burst) for a in archs]
    svc = np.empty((b_n, m), dt)
    for b, (a, hw) in enumerate(zip(archs, hws)):
        flits = np.maximum(1, -(-(payload + hbytes) // (a.bus_bits // 8)))
        svc[b] = ((flits + hw["stall"]) / (hw["fclk"] * hw["eta"])).astype(dt)
    in_free = np.zeros((b_n, n), dt)
    out_free = np.zeros((b_n, n), dt)
    dep_end = np.empty((b_n, m), dt)
    svc_t = np.ascontiguousarray(svc.T)
    for k in range(m):
        i, j = src[k], dst[k]
        start = np.maximum(np.maximum(t[k], in_free[:, i]), out_free[:, j])
        end = start + svc_t[k]
        in_free[:, i] = end
        out_free[:, j] = end
        dep_end[:, k] = end
    qid = src * n + dst
    queues = [np.nonzero(qid == q)[0] for q in np.unique(qid)]
    out = []
    wire_bits = float((payload + hbytes).sum() * 8)
    for b, hw in enumerate(hws):
        pipe = dt.type((hw["pipeline"] + hw["arb"]) / hw["fclk"])
        lat = ((dep_end[b] - t + pipe) * dt.type(1e9)).astype(np.float64)
        occ = np.zeros(m, np.int64)
        for sel in queues:
            departed = np.searchsorted(dep_end[b, sel], t[sel], side="right")
            occ[sel] = np.arange(sel.size) - departed
        duration = max(float(dep_end[b].max()) - float(t.min()), 1e-12)
        out.append({"latency_ns": lat, "occupancy": occ,
                    "departure_s": dep_end[b].astype(np.float64),
                    "p99": float(np.percentile(lat, 99)),
                    "throughput_gbps": wire_bits / duration / 1e9})
    return out


# ------------------------------------------------------------------ stage 4

def _arrivals(t0, src, wire, link_bps, n, rnd):
    """Host stack + NIC: serialise onto the source link, then propagate."""
    host_free = [0.0] * n
    arr = [0.0] * len(t0)
    for k in np.argsort(np.asarray(t0), kind="stable").tolist():
        s = src[k]
        start = max(t0[k], host_free[s])
        tx = rnd(wire[k] * 8 / link_bps)
        host_free[s] = rnd(start + tx)
        arr[k] = rnd(rnd(start + tx) + PROP_DELAY_S)
    return arr


def netsim(a: Arch, dep: Deployment, trace, hw: Dict[str, float],
           dtype=np.float64) -> dict:
    """Finite-VOQ event simulation: one heap of switch arrivals, a FIFO of
    departures per VOQ (and one for a shared buffer), drop when full."""
    rnd = _rounder(dtype)
    n = a.n_ports
    link_bps = trace["link_gbps"] * 1e9
    t0 = [rnd(x) for x in np.asarray(trace["time_s"], np.float64).tolist()]
    src = (np.asarray(trace["src"], np.int64) % n).tolist()
    dst = (np.asarray(trace["dst"], np.int64) % n).tolist()
    wire = (np.asarray(trace["payload_bytes"], np.int64)
            + header_bytes(dep)).tolist()
    m = len(t0)
    fclk, eta = hw["fclk"], hw["eta"]
    fb = a.bus_bits // 8
    svc = [rnd(max((max(1, -(-w // fb)) / fclk + hw["stall"] / fclk) / eta,
                   w * 8 / (link_bps * eta))) for w in wire]
    pipe_s = rnd((hw["pipeline"] + hw["arb"]) / fclk)
    arr = _arrivals(t0, src, wire, link_bps, n, rnd)
    events = [(arr[k], k) for k in range(m)]
    heapq.heapify(events)
    in_free = [0.0] * n
    out_free = [0.0] * n
    q_dep: Dict[int, List[float]] = {}
    shared: List[float] = []
    shared_cap = n * a.voq_depth
    is_shared = a.voq == "shared"
    latency = np.full(m, np.nan)
    drops, delivered_bits, t_end = 0, 0.0, 0.0
    while events:
        now, k = heapq.heappop(events)
        i, j = src[k], dst[k]
        d = q_dep.setdefault(i * n + j, [])
        while d and d[0] <= now:
            heapq.heappop(d)
        full = len(d) >= a.voq_depth
        if is_shared:
            while shared and shared[0] <= now:
                heapq.heappop(shared)
            full = full or len(shared) >= shared_cap
        if full:
            drops += 1
            continue
        start = max(rnd(now + pipe_s), in_free[i], out_free[j])
        end = rnd(start + svc[k])
        in_free[i] = end
        out_free[j] = end
        heapq.heappush(d, end)
        if is_shared:
            heapq.heappush(shared, end)
        latency[k] = rnd(rnd(rnd(end + PROP_DELAY_S) - t0[k]) * 1e9)
        delivered_bits += wire[k] * 8
        t_end = max(t_end, end)
    lat = latency[~np.isnan(latency)]
    duration = max(t_end - (min(t0) if m else 0.0), 1e-12)
    return {"latency_full_ns": latency,
            "p99": float(np.percentile(lat, 99)) if lat.size else math.inf,
            "mean": float(lat.mean()) if lat.size else math.inf,
            "drop_rate": drops / max(m, 1),
            "throughput_gbps": delivered_bits / duration / 1e9}


def verify_rows(archs: Sequence[Arch], dep: Deployment, trace,
                dtype=np.float64) -> List[dict]:
    i_burst = idc(trace["time_s"])
    return [netsim(a, dep, trace, hardware(a, dep, i_burst), dtype)
            for a in archs]


# --------------------------------------------------------------- Algorithm 1

def _admits(res: Dict[str, float], budget: Dict[str, float]) -> bool:
    return all(res.get(k, 0.0) <= v for k, v in budget.items())


def _feasible(v: dict, dep: Deployment) -> bool:
    return v["p99"] <= dep.p99_latency_ns and v["drop_rate"] <= dep.drop_rate * 1.5


def _pareto(items, key):
    pts = [key(it) for it in items]
    keep = []
    for i, p in enumerate(pts):
        dominated = any(q[0] <= p[0] and q[1] <= p[1] and q != p
                        for j, q in enumerate(pts) if j != i)
        keep.append(not dominated)
    return [it for it, k in zip(items, keep) if k]


def stage3(rows: Sequence[Tuple[Arch, dict]], dep: Deployment) -> List[Arch]:
    """Explore the top-k by surrogate p99 plus the best of each (scheduler,
    buffer) family; size each; keep those within the budget."""
    valid = sorted(rows, key=lambda r: r[1]["p99"])
    explored = list(valid[:dep.top_k if dep.top_k > 0 else len(valid)])
    seen = {r[0] for r in explored}
    families: Dict[Tuple[str, str], Tuple[Arch, dict]] = {}
    for a, sr in valid:
        families.setdefault((a.sched, a.voq), (a, sr))
    for a, sr in families.values():
        if a not in seen:
            explored.append((a, sr))
    sized = []
    for a, sr in explored:
        s = size(a, sr["occupancy"], dep.drop_rate)
        if _admits(synthesize(s, dep), dep.budget):
            sized.append(s)
    return sized


def front(verified: Sequence[Tuple[Arch, dict]], dep: Deployment):
    """(front, best): non-dominated (mean latency, BRAM) among feasible rows
    (all rows when none is feasible); best is the first feasible minimum."""
    feas = [(a, v) for a, v in verified if _feasible(v, dep)] or list(verified)
    key = lambda av: (av[1]["mean"], synthesize(av[0], dep)["brams"])
    best = None
    for a, v in verified:
        if _feasible(v, dep) and (best is None or key((a, v)) < key(best)):
            best = (a, v)
    return (_pareto(feas, key) if feas else []), best


def explore(dep: Deployment, trace, dtype=np.float64) -> dict:
    """Exhaustive Algorithm 1: every stage-2 row, every sized row, the
    verified rows, the front and the champion."""
    active = stage1(enumerate_templates(dep), dep, trace)
    srs = surrogate(active, dep, trace, dtype)
    valid = [(a, sr) for a, sr in zip(active, srs)
             if sr["p99"] <= dep.p99_latency_ns]
    sized = stage3(valid, dep)
    verified = list(zip(sized, verify_rows(sized, dep, trace, dtype)))
    fr, best = front(verified, dep)
    return {"stage2": dict(zip(active, srs)), "sized": sized,
            "stage4": dict(verified), "front": fr, "best": best}
