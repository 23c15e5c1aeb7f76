"""The two readers of the sweep counters, ``sweeps.explore`` and
``scan_fallbacks.explore``, on the hand-made record of two explorations of
``test_span_readers.py`` with the counters noted on its device calls."""

import pytest

from repro.analysis import spans
from test_span_readers import RECORDS, _ctx, _reader

#: (sweeps, scan_fallback) per call, keyed by (exploration root, span name)
NOTED = {(0, "spac.stage2.scan"): (3, 0),
         (0, "spac.stage4.round1"): (5, 0),
         (2000, "spac.stage2.scan"): (64, 1),
         (2000, "spac.stage4.round1"): (6, 0)}


def _noted(rec):
    got = NOTED.get((rec.root, rec.name))
    if got is None:
        return rec
    return rec._replace(attrs=dict(rec.attrs, sweeps=got[0],
                                   scan_fallback=got[1]))


SWEPT = [_noted(r) for r in RECORDS]

WANT = {
    # (3 + 5 + 64 + 6) / 4 calls; one fallback over two explorations
    "sweeps.explore": 19.5,
    "scan_fallbacks.explore": 0.5,
}


@pytest.fixture
def recorded(monkeypatch):
    def use(recs, dropped=0):
        monkeypatch.setattr(spans, "records", lambda: list(recs))
        monkeypatch.setattr(spans, "dropped", lambda: dropped)
    return use


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value(recorded, name):
    recorded(SWEPT)
    assert _reader(name)(_ctx()) == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("case", ["empty", "dropped", "no_root",
                                  "no_counters"])
def test_reader_reads_nothing(recorded, name, case):
    """No roots, dropped spans, or a program that notes neither counter
    (one without the sweep form): the metric is left out of the line."""
    if case == "empty":
        recorded([])
    elif case == "dropped":
        recorded(SWEPT, dropped=1)
    elif case == "no_root":
        recorded([r for r in SWEPT if r.name != "spac.explore"])
    else:
        recorded(RECORDS)
    assert _reader(name)(_ctx()) is None
