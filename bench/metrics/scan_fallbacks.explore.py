"""Stage-2 and round-1 calls per exploration whose fixed-point sweeps did
not settle within the cap and fell back to the serial scan: the
``scan_fallback`` counter on the ``spac.stage2.scan`` and
``spac.stage4.round1`` spans, over the completed ``spac.explore`` roots of
the traced window.  A program that notes no such counter reads nothing."""

CALLS = ("spac.stage2.scan", "spac.stage4.round1")


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    noted = [r.attrs["scan_fallback"] for r in recs
             if r.root in roots and r.name in CALLS
             and "scan_fallback" in r.attrs]
    return float(sum(noted)) / len(roots) if noted else None
