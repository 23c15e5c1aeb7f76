"""Mean seconds per exploration that the NSGA-II search spends in its own
host steps, ask and tell (decode, stage-1 prune, dedup, ranking and
breeding): the program's ``spac.search.ask`` and ``spac.search.tell`` spans
over the completed ``spac.explore`` roots of the traced window.  An
exhaustive exploration, or a program without these spans, reads
nothing."""

NAMES = ("spac.search.ask", "spac.search.tell")


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    steps = [r.end_ns - r.start_ns for r in recs
             if r.root in roots and r.name in NAMES]
    return sum(steps) * 1e-9 / len(roots) if steps else None
