"""Mean seconds per exploration in problem build (trace load, feature
analysis, binding, problem set-up): the program's ``spac.build`` spans
over the completed ``spac.explore`` roots of the traced window."""


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    ns = sum(r.end_ns - r.start_ns for r in recs
             if r.root in roots and r.name == "spac.build")
    return ns * 1e-9 / len(roots)
