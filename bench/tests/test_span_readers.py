"""The eight readers of the program's spans, on a hand-made record of two
explorations and the hand-made device trace of ``test_devtrace.py``."""

import importlib.util
import os

import pytest

from repro.analysis import spans
from repro.analysis.spans import Record
from test_devtrace import SYNTH

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _explore(base, rounds, fallback):
    """One exploration of 1000 ns starting at ``base``: ids base..base+n."""
    rows = [("spac.explore", 0, 1000, None, {}),
            ("spac.build", 0, 100, 0, {}),
            ("spac.stage1", 100, 150, 0, {}),
            ("spac.stage2", 150, 450, 0, {"rows": 4, "events": 8}),
            ("spac.stage2.scan", 200, 400, 3, {}),
            ("spac.screen", 450, 500, 0, {}),
            ("spac.stage3", 500, 550, 0, {}),
            ("spac.stage4", 550, 900, 0, {"rounds": rounds,
                                          "fallback_rows": fallback}),
            ("spac.stage4.round1", 560, 760, 7, {}),
            ("spac.stage4.replay", 780, 800, 7, {"round": 2}),
            ("spac.finalize", 900, 980, 0, {})]
    return [Record(n, base + a, base + b, base + i,
                   None if p is None else base + p, base, attrs)
            for i, (n, a, b, p, attrs) in enumerate(rows)]


#: two explorations, and one served request that is not an exploration
RECORDS = (_explore(0, 1, 0) + _explore(2000, 3, 2)
           + [Record("spac.serve.request", 3000, 9000, 50, None, 50, {}),
              Record("spac.build", 3000, 8000, 51, 50, 50, {})])

#: per exploration: (build, stages 1/3 + screen + finalize, stage-2 host,
#: stage-4 host, device calls) partition its children, 980 of its 1000 ns
WANT = {
    "build_s.explore": 100e-9,
    "dse_host_s.explore": 230e-9,
    "stage2_host_s.explore": 100e-9,
    "stage4_host_s.explore": 130e-9,
    "calls_s.explore": 420e-9,
    # the synthetic chip is busy 35 ns against 840 ns inside the calls
    "call_overhead.explore": 100.0 * (1 - 35 / 840),
    "stage4_rounds.explore": 2.0,
    "fallback_rows.explore": 1.0,
}


def _ctx():
    return {"events": SYNTH, "win": (0.0, 100.0)}


@pytest.fixture
def recorded(monkeypatch):
    def use(recs, dropped=0):
        monkeypatch.setattr(spans, "records", lambda: list(recs))
        monkeypatch.setattr(spans, "dropped", lambda: dropped)
    return use


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value(recorded, name):
    recorded(RECORDS)
    assert _reader(name)(_ctx()) == pytest.approx(WANT[name], rel=1e-12)


def test_readers_partition_the_exploration():
    parts = ("build_s.explore", "dse_host_s.explore", "stage2_host_s.explore",
             "stage4_host_s.explore", "calls_s.explore")
    assert sum(WANT[p] for p in parts) == pytest.approx(980e-9)


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("case", ["empty", "dropped", "no_root"])
def test_reader_reads_nothing(recorded, name, case):
    if case == "empty":
        recorded([])
    elif case == "dropped":
        recorded(RECORDS, dropped=1)
    else:
        recorded([r for r in RECORDS if r.name != "spac.explore"])
    assert _reader(name)(_ctx()) is None
