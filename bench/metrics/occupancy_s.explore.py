"""Mean seconds per exploration in the exact queue-occupancy count of stage
2: the program's ``spac.stage2.occupancy`` spans (inside
``spac.stage2.reduce``) over the completed ``spac.explore`` roots of the
traced window.  A program that has no such span reads nothing."""


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    occ = [r.end_ns - r.start_ns for r in recs
           if r.root in roots and r.name == "spac.stage2.occupancy"]
    return sum(occ) * 1e-9 / len(roots) if occ else None
