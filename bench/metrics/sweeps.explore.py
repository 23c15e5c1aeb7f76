"""Mean fixed-point sweeps per stage-2 and round-1 call: the ``sweeps``
counter the program notes on each ``spac.stage2.scan`` and
``spac.stage4.round1`` span (the longest busy chain plus one, or the cap
when the call fell back to the serial scan), over the completed
``spac.explore`` roots of the traced window.  A program that notes no
sweeps reads nothing."""

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
    sweeps = [r.attrs["sweeps"] for r in recs
              if r.root in roots and r.name in CALLS and "sweeps" in r.attrs]
    return sum(sweeps) / len(sweeps) if sweeps else None
