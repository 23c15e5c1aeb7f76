"""Stage-2 rows carry the quantiles stage 2 already took.

``BatchedSurrogateResult.results()`` hands each row its slice of the batch's
``quantiles``, and ``SurrogateResult.p(q)`` reads it there instead of taking
a new percentile over the row's latency samples.  The carried value must be
the percentile bit for bit, an empty trace must still read ``inf``, a ``q``
that is not carried falls back (and is noted as a miss), and whole
explorations must report the same with and without the carried values.
"""

import math

import numpy as np
import pytest

import repro.core.dse as dse
import repro.sim.batched_surrogate as bs
from repro.api import registry, run_scenario
from repro.api.golden import VOLATILE
from repro.core import (ArchRequest, SearchSpec, bind, compressed_protocol,
                        enumerate_candidates)
from repro.core.dse import SurrogateResult
from repro.launch.mesh import bucket_size
from repro.sim import run_surrogate_batched
from repro.traces import datacenter, hft
from repro.traces.base import Trace

BOUND = bind(compressed_protocol(addr_bits=4, length_bits=6), flit_bits=256)
QS = (50, 90, 99)


def _candidates(n):
    return enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:n]


@pytest.fixture
def noted(monkeypatch):
    """The counts ``p()`` notes, summed (as a recording span would)."""
    got = {}

    def spy(**counts):
        for k, v in counts.items():
            got[k] = got.get(k, 0) + v

    monkeypatch.setattr(dse, "note", spy)
    return got


@pytest.mark.parametrize("events", [7, 300, 2048])
@pytest.mark.parametrize("width", [3, 8, 17])
def test_carried_quantiles_equal_the_percentile(events, width, noted):
    """3 and 17 rows pad to buckets of 8 and 24; every real row's carried
    quantile is ``np.percentile`` of its own latency row, bit for bit."""
    assert bucket_size(3) != 3 and bucket_size(17) != 17
    tr = hft(seed=events).head(events)
    assert len(tr) == events
    rows = run_surrogate_batched(_candidates(width), BOUND, tr,
                                 back_annotation=False).results()
    assert len(rows) == width
    for sr in rows:
        assert set(sr.quantiles) == {float(q) for q in QS}
        for q in QS:
            want = float(np.percentile(sr.latency_ns, q))
            assert sr.p(q) == want
            assert sr.p(float(q)) == want
            assert sr.quantiles[float(q)] == want
    assert noted == {"quantile_reads": 6 * width}


def test_carried_quantiles_across_port_groups(noted):
    """Mixed port counts run as separate groups and are stitched back: each
    row still carries its own quantiles."""
    tr = datacenter(seed=3, n_ports=16, duration_s=200e-6)
    archs = (enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:5]
             + enumerate_candidates(ArchRequest(n_ports=16, addr_bits=4))[:4])
    rows = run_surrogate_batched(archs, BOUND, tr,
                                 back_annotation=False).results()
    for sr in rows:
        assert sr.p(99) == float(np.percentile(sr.latency_ns, 99))
    assert noted == {"quantile_reads": len(archs)}


def test_empty_trace_reads_inf(noted):
    empty = Trace("empty", np.zeros(0), np.zeros(0, np.int32),
                  np.zeros(0, np.int32), np.zeros(0, np.int64), 8)
    rows = run_surrogate_batched(_candidates(4), BOUND, empty,
                                 back_annotation=False).results()
    for sr in rows:
        assert sr.quantiles == {}
        assert sr.p(99) == math.inf
    assert noted == {"quantile_reads": 4}


def test_uncarried_quantile_falls_back_as_a_miss(noted):
    tr = hft(seed=5).head(512)
    sr = run_surrogate_batched(_candidates(2), BOUND, tr,
                               back_annotation=False).results()[0]
    assert 99.9 not in sr.quantiles
    assert sr.p(99.9) == float(np.percentile(sr.latency_ns, 99.9))
    assert noted == {"quantile_reads": 1, "quantile_misses": 1}
    # a result that carries nothing takes the percentile for every q
    bare = SurrogateResult(q_occupancy=sr.q_occupancy,
                           latency_ns=sr.latency_ns)
    assert bare.p(99) == sr.p(99)
    assert noted == {"quantile_reads": 3, "quantile_misses": 2}


def _report(scenario):
    got = run_scenario(scenario).to_dict()
    for k in VOLATILE:
        got.pop(k)
    return got


@pytest.mark.parametrize("search", [None, SearchSpec(population=16,
                                                     generations=3, seed=5)],
                         ids=["exhaustive", "nsga2"])
def test_reports_identical_without_carried_quantiles(search, monkeypatch,
                                                     noted):
    """Clearing every row's carried quantiles (the percentile path) changes
    nothing in an exploration's report; keeping them leaves no miss."""
    s = registry["hft"].override(back_annotation=False, top_k=3,
                                 trace_params={"duration_s": 8e-5},
                                 search=search)
    kept = _report(s)
    assert noted.get("quantile_reads", 0) > 0
    assert noted.get("quantile_misses", 0) == 0

    results = bs.BatchedSurrogateResult.results

    def cleared(self):
        out = results(self)
        for sr in out:
            sr.quantiles = {}
        return out

    monkeypatch.setattr(bs.BatchedSurrogateResult, "results", cleared)
    noted.clear()
    assert _report(s) == kept
    assert noted["quantile_misses"] == noted["quantile_reads"] > 0
