"""Share of the candidate rows sent to the device that are padding, in
percent: the ``pad_rows`` the program notes on each stage-2 and stage-4
device call (``spac.stage2.scan``, ``spac.stage4.round1``, ``.replay``,
``.scan``), over those calls' real ``rows`` plus their pad rows, in the
completed ``spac.explore`` roots of the traced window.  A program that
notes no pad rows reads nothing."""

CALLS = ("spac.stage2.scan", "spac.stage4.round1", "spac.stage4.replay",
         "spac.stage4.scan")


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    calls = [r.attrs for r in recs if r.root in roots and r.name in CALLS
             and "pad_rows" in r.attrs and "rows" in r.attrs]
    total = sum(a["rows"] + a["pad_rows"] for a in calls)
    if total <= 0:
        return None
    return 100.0 * sum(a["pad_rows"] for a in calls) / total
