"""Frozen copies of the two trace generators the benchmark's cells use.

These are copies of ``repro.traces.workloads.hft`` / ``datacenter`` and of
the ``poisson_times`` / ``burst_times`` helpers as they stood when the
benchmark was defined.  The benchmark makes its traffic with these, never
with the program's own generators, so a change to the program cannot change
the work it is measured on.  A trace is a dict of parallel arrays sorted by
arrival time (stable), the order ``repro.traces.Trace`` keeps.
"""

from __future__ import annotations

import numpy as np

__all__ = ["GENERATORS", "make_trace", "head", "save_npz"]


def poisson_times(rng, rate_pps, duration_s):
    n = rng.poisson(rate_pps * duration_s)
    return np.sort(rng.uniform(0.0, duration_s, size=n))


def burst_times(rng, burst_rate_ps, duration_s, burst_len_mean, intra_gap_s):
    """Poisson bursts of geometric length with fixed intra-burst spacing."""
    starts = poisson_times(rng, burst_rate_ps, duration_s)
    out = []
    for s in starts:
        blen = 1 + rng.geometric(1.0 / max(burst_len_mean, 1.0))
        out.append(s + np.arange(blen) * intra_gap_s)
    if not out:
        return np.zeros(0)
    return np.sort(np.concatenate(out))


def _trace(name, times, srcs, dsts, sizes, n_ports, link_gbps):
    t = np.asarray(times, np.float64)
    order = np.argsort(t, kind="stable")
    return {
        "name": name,
        "time_s": t[order],
        "src": np.asarray(srcs, np.int32)[order],
        "dst": np.asarray(dsts, np.int32)[order],
        "payload_bytes": np.asarray(sizes, np.int64)[order],
        "n_ports": int(n_ports),
        "link_gbps": float(link_gbps),
    }


def hft(seed=0, n_ports=8, duration_s=400e-6, link_gbps=10.0, load=0.35):
    """Market-data bursts: feed ports 0-1 burst (mean 8), the others mean 2;
    24 B payloads; subscribers cluster on ports 2-4."""
    rng = np.random.default_rng(seed)
    payload = 24
    pkt_s = (payload + 42) * 8 / (link_gbps * 1e9)
    per_src_rate = load * link_gbps * 1e9 / ((payload + 42) * 8) / n_ports
    times, srcs, dsts = [], [], []
    for s in range(n_ports):
        bursty = s < 2
        t = burst_times(
            rng,
            burst_rate_ps=per_src_rate / (8.0 if bursty else 2.0),
            duration_s=duration_s,
            burst_len_mean=8.0 if bursty else 2.0,
            intra_gap_s=pkt_s * 1.05,
        )
        times.append(t)
        srcs.append(np.full(t.size, s))
        pref = np.array([0.05, 0.05, 0.3, 0.25, 0.15, 0.08, 0.07, 0.05][:n_ports])
        pref[s] = 0.0
        pref = pref / pref.sum()
        dsts.append(rng.choice(n_ports, size=t.size, p=pref))
    n = sum(t.size for t in times)
    return _trace("hft", np.concatenate(times), np.concatenate(srcs),
                  np.concatenate(dsts), np.full(n, payload), n_ports, link_gbps)


def datacenter(seed=0, n_ports=32, duration_s=800e-6, link_gbps=25.0,
               load=0.2, mean_payload=965.5):
    """Microservice RPC: Poisson per source, Zipf(1.1) destinations,
    lognormal(log 200, 1.3) payloads scaled to the published mean."""
    rng = np.random.default_rng(seed)
    mean_wire = mean_payload + 42
    rate = load * link_gbps * 1e9 / (mean_wire * 8) / n_ports
    times, srcs, dsts, sizes = [], [], [], []
    ranks = np.arange(1, n_ports + 1, dtype=np.float64)
    zipf = (1.0 / ranks**1.1)
    for s in range(n_ports):
        t = poisson_times(rng, rate, duration_s)
        times.append(t)
        srcs.append(np.full(t.size, s))
        p = zipf.copy()
        p[s] = 0.0
        p /= p.sum()
        dsts.append(rng.choice(n_ports, size=t.size, p=p))
        raw = rng.lognormal(mean=np.log(200), sigma=1.3, size=t.size)
        sizes.append(np.clip(raw * (mean_payload / max(raw.mean(), 1.0)), 4,
                             9000).astype(np.int64))
    return _trace("datacenter", np.concatenate(times), np.concatenate(srcs),
                  np.concatenate(dsts), np.concatenate(sizes), n_ports,
                  link_gbps)


GENERATORS = {"hft": hft, "datacenter": datacenter}


def head(trace, n):
    """The first ``n`` events by arrival time."""
    out = dict(trace)
    for k in ("time_s", "src", "dst", "payload_bytes"):
        out[k] = trace[k][:n]
    return out


def make_trace(generator, params, seed, events):
    """A trace of exactly ``events`` events: generate over ``duration_s``
    and keep the first ``events`` by arrival time, lengthening the duration
    (by half, deterministically) until the seed yields enough events."""
    fn = GENERATORS[generator]
    kw = dict(params)
    duration = float(kw.pop("duration_s"))
    while True:
        tr = fn(seed=seed, duration_s=duration, **kw)
        if tr["time_s"].size >= events:
            return head(tr, events)
        duration *= 1.5


def save_npz(trace, path):
    """Write the trace in the layout ``repro.traces.Trace.load`` reads."""
    np.savez(path, time_s=trace["time_s"], src=trace["src"],
             dst=trace["dst"], payload_bytes=trace["payload_bytes"],
             meta_name=np.asarray(trace["name"]),
             meta_n_ports=np.asarray(trace["n_ports"], np.int64),
             meta_link_gbps=np.asarray(trace["link_gbps"], np.float64))
