"""The reader of the stage-2 quantile counters, ``quantile_misses.explore``,
on the hand-made record of two explorations of ``test_span_readers.py``
with ``quantile_reads`` and ``quantile_misses`` noted on its host steps."""

import pytest

from repro.analysis import spans
from test_span_readers import RECORDS, _ctx, _reader

NAME = "quantile_misses.explore"

#: (quantile_reads, quantile_misses) per span, keyed by (exploration root,
#: span name); a miss count of None is a span that noted reads only
NOTED = {(0, "spac.screen"): (4, None),
         (0, "spac.stage3"): (4, 1),
         (2000, "spac.screen"): (4, 2),
         (2000, "spac.stage3"): (4, 3)}


def _noted(rec, table):
    got = table.get((rec.root, rec.name))
    if got is None:
        return rec
    reads, misses = got
    attrs = dict(rec.attrs, quantile_reads=reads)
    if misses is not None:
        attrs["quantile_misses"] = misses
    return rec._replace(attrs=attrs)


@pytest.fixture
def recorded(monkeypatch):
    def use(recs, dropped=0):
        monkeypatch.setattr(spans, "records", lambda: list(recs))
        monkeypatch.setattr(spans, "dropped", lambda: dropped)
    return use


def test_misses_summed_over_roots(recorded):
    """(0 + 1 + 2 + 3) misses over two explorations."""
    recorded([_noted(r, NOTED) for r in RECORDS])
    assert _reader(NAME)(_ctx()) == pytest.approx(3.0, rel=1e-12)


def test_reads_without_misses_read_zero(recorded):
    """A program whose rows carry every quantile it reads notes reads only:
    the metric reads 0, not nothing."""
    reads_only = {k: (v[0], None) for k, v in NOTED.items()}
    recorded([_noted(r, reads_only) for r in RECORDS])
    assert _reader(NAME)(_ctx()) == 0.0


def test_request_spans_outside_explorations_ignored(recorded):
    recs = [_noted(r, NOTED) for r in RECORDS]
    recs = [r._replace(attrs=dict(r.attrs, quantile_reads=9,
                                  quantile_misses=9))
            if r.root == 50 else r for r in recs]
    recorded(recs)
    assert _reader(NAME)(_ctx()) == pytest.approx(3.0, rel=1e-12)


@pytest.mark.parametrize("case", ["empty", "parent", "dropped", "no_root"])
def test_reader_reads_nothing(recorded, case):
    """Nothing recorded, spans of a program that notes no
    ``quantile_reads`` (the one before the carried quantiles), dropped
    spans, or no exploration root: the metric is left out of the line."""
    noted = [_noted(r, NOTED) for r in RECORDS]
    if case == "empty":
        recorded([])
    elif case == "parent":
        recorded(RECORDS)
    elif case == "dropped":
        recorded(noted, dropped=1)
    else:
        recorded([r for r in noted if r.name != "spac.explore"])
    assert _reader(NAME)(_ctx()) is None
