"""The readers of the search, pad-row and occupancy spans and counters,
``search_host_s``, ``pad_share`` and ``occupancy_s`` (each ``.explore``),
and ``search_s.explore``, on hand-made records: two NSGA-II explorations whose
device calls note their real and pad rows, and one served request that is
not an exploration."""

import pytest

from repro.analysis import spans
from repro.analysis.spans import Record
from test_span_readers import _ctx, _reader


def _search(base, pads):
    """One NSGA-II exploration of 1000 ns starting at ``base``: two
    generations (ask, a stage-2 call, tell), then stage 4 with round 1 and
    one replay.  ``pads`` holds the pad rows of the four device calls."""
    p2a, p2b, p4, pr = pads
    rows = [("spac.explore", 0, 1000, None, {}),
            ("spac.build", 0, 50, 0, {}),
            ("spac.search", 50, 600, 0, {"generations": 2,
                                         "evaluated_rows": 20}),
            ("spac.search.ask", 50, 80, 2, {"generation": 0}),
            ("spac.stage2", 80, 250, 2, {"rows": 12, "events": 8}),
            ("spac.stage2.scan", 100, 200, 4, {"rows": 12, "pad_rows": p2a}),
            ("spac.stage2.reduce", 200, 250, 4, {}),
            ("spac.stage2.occupancy", 210, 240, 6, {"rows": 12,
                                                   "events": 8}),
            ("spac.search.tell", 250, 300, 2, {"generation": 0}),
            ("spac.search.ask", 300, 320, 2, {"generation": 1}),
            ("spac.stage2", 320, 500, 2, {"rows": 8, "events": 8}),
            ("spac.stage2.scan", 330, 450, 10, {"rows": 8, "pad_rows": p2b}),
            ("spac.stage2.reduce", 450, 500, 10, {}),
            ("spac.stage2.occupancy", 460, 480, 12, {"rows": 8, "events": 8}),
            ("spac.search.tell", 500, 600, 2, {"generation": 1}),
            ("spac.stage4", 600, 900, 0, {"rows": 5, "unique_rows": 5}),
            ("spac.stage4.round1", 610, 700, 15, {"rows": 5, "pad_rows": p4}),
            ("spac.stage4.replay", 750, 800, 15, {"round": 2, "rows": 2,
                                                  "pad_rows": pr})]
    return [Record(n, base + a, base + b, base + i,
                   None if p is None else base + p, base, attrs)
            for i, (n, a, b, p, attrs) in enumerate(rows)]


#: two explorations, and one served request that is not an exploration
RECORDS = (_search(0, (4, 0, 3, 6)) + _search(2000, (4, 0, 3, 6))
           + [Record("spac.serve.request", 3000, 9000, 50, None, 50, {}),
              Record("spac.stage2.occupancy", 3000, 8000, 51, 50, 50, {}),
              Record("spac.search.ask", 3000, 8000, 52, 50, 50, {})])

WANT = {
    # ask 30 + tell 50 + ask 20 + tell 100 ns per exploration
    "search_host_s.explore": 200e-9,
    # two occupancy counts of 30 and 20 ns per exploration
    "occupancy_s.explore": 50e-9,
    # pad 4 + 0 + 3 + 6 = 13 of 12 + 8 + 5 + 2 = 27 real rows, per exploration
    "pad_share.explore": 100.0 * 13 / 40,
}


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


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("case", ["empty", "dropped", "no_root",
                                  "not_noted"])
def test_reader_reads_nothing(recorded, name, case):
    """No roots, dropped spans, or a program without these spans and
    counters (the one before them): the metric is left out of the line."""
    if case == "empty":
        recorded([])
    elif case == "dropped":
        recorded(RECORDS, dropped=1)
    elif case == "no_root":
        recorded([r for r in RECORDS if r.name != "spac.explore"])
    else:
        gone = {"spac.search.ask", "spac.search.tell",
                "spac.stage2.occupancy"}
        recorded([r._replace(attrs={k: v for k, v in r.attrs.items()
                                    if k != "pad_rows"})
                  for r in RECORDS if r.name not in gone])
    assert _reader(name)(_ctx()) is None


def test_pad_share_reads_zero_without_padding(recorded):
    recorded(_search(0, (0, 0, 0, 0)))
    assert _reader("pad_share.explore")(_ctx()) == 0.0


@pytest.mark.parametrize("search", [True, False])
def test_search_s_reads_only_searches(search):
    ctx = {"search": search, "explorations": [
        {"wall_s": 1.0, "stage2_s": 0.25, "stage4_s": 0.5},
        {"wall_s": 1.0, "stage2_s": 0.75, "stage4_s": 0.5}]}
    got = _reader("search_s.explore")(ctx)
    assert got == (0.5 if search else None)
