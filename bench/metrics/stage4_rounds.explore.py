"""Mean fixed-point rounds per stage-4 call: the ``rounds`` counter the
program notes on each ``spac.stage4`` span (1 when every row settles in
the fused first round), over the completed ``spac.explore`` roots of the
traced window."""


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    rounds = [r.attrs["rounds"] for r in recs
              if r.root in roots and r.name == "spac.stage4"
              and "rounds" in r.attrs]
    return sum(rounds) / len(rounds) if rounds else None
